"""Serving decode loop: the continuous-batching engine over the paged KV
cache.

This is the TPU replacement for the reference's inference hot path
(AnalysisPredictor decode loop over fused_multi_transformer with its CUDA
KV cache — SURVEY.md §2.2/§3.5): ONE jitted program, the model module's
``ragged_step`` under a ``lax.scan`` of micro-rounds, serves mixed
prefill and decode rows from a host-planned packed layout, the paged
pools donated between steps so a round costs one host round-trip.

Shapes never follow the request mix — the dynamic-shape story on XLA
(SURVEY §2.5 CINN row) is row metadata padded to the fixed slot count,
not a compile per prompt bucket.
"""

from __future__ import annotations

import collections
import importlib
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..flags import flag_value
from ..observability.events import emit_event
from ..observability.journal import token_checksum
from ..observability.memory import memory_armed, memory_ledger
from ..observability.profiling import chain_armed as _chain_armed
from ..observability.profiling import note_chain as _note_chain
from ..observability.runtime import collections as gc_collections
from ..observability.runtime import recompiles
from ..profiler.record import emit_spans, make_span, phase, spans_armed
from . import constrain as _constrain
from . import sampling as _sampling
from .sampling import SamplerConfig


def _prefill_flags() -> Tuple:
    """Mutable host state the prefill/unified programs bake in at trace
    time (``llama._mm_prefill`` reads FLAGS_serving_a8w8_prefill to pick
    the int8 prefill matmul; the kernel-backend selectors in
    ``ops/_common.use_pallas`` and ``ops/rms_norm._use_pallas_rms`` read
    their flags the same way). Every compile-cache key that guards such
    a program includes this tuple, so a ``set_flags`` flip RETRACES — a
    counted ``paddle_runtime_recompiles_total`` miss — instead of
    silently keeping the stale program. The backend flags were the
    cache-key rule's first triage catch (tpu-lint: trace-host-state +
    cache-key): before PR 15 a ``use_pallas_*`` flip kept serving the
    old backend's program forever."""
    return (bool(flag_value("serving_a8w8_prefill")),
            bool(flag_value("use_pallas_kernels")),
            bool(flag_value("use_pallas_rms_norm")))


@dataclass
class GenerationConfig:
    max_new_tokens: int = 32
    temperature: float = 1.0
    top_k: int = 0            # 0 = off
    top_p: float = 1.0        # 1.0 = off
    do_sample: bool = False   # False = greedy
    eos_token_id: Optional[int] = None
    pad_token_id: int = 0
    seed: int = 0


_LLAMA = "paddle_tpu.models.llama"
_MODEL_PROTOCOL = ("ragged_step", "init_stacked_params",
                   "serving_param_specs", "shard_params_tp")


def _serving_module(model_config):
    """The module that holds the model's serving step: named by the
    config's class (``serving_module``), Llama's where it names none.

    The model protocol — every name of a model module the engine looks
    up. REQUIRED: ``ragged_step(params, ids, token_row, positions,
    kv_lens, last_idx, *pools, block_tables, config, mesh=, mp_axis=,
    logits_epilogue=) -> (logits, *pools[, aux])``, ``pools`` the cache's
    arrays (``k_pages, v_pages`` unless the module says otherwise),
    ``init_stacked_params(config)``, ``serving_param_specs(config)``,
    ``shard_params_tp(params, mesh, config)``. OPTIONAL:
    ``cache_layout(config)`` (what a token keeps per layer, an
    ``ops.paged_attention.CacheLayout``; K and V of
    ``num_key_value_heads`` x ``head_dim`` where it names none),
    ``attention_windows(config)`` (per layer the sliding window, None
    where attention is full), ``state_layout(config)`` (what a ROW keeps in
    the model's recurrent layers, a ``kvcache.state.StateLayout``: the
    step then takes and returns the state's arrays after the cache's, in
    ``pools``, and starts a row whose first token is at position 0 from
    zeros) and the step's last return value (a small int32 routing
    record, handed out with the tokens)."""
    name = getattr(model_config, "serving_module", _LLAMA)
    module = importlib.import_module(name)
    missing = [n for n in _MODEL_PROTOCOL if not hasattr(module, n)]
    if missing:
        raise ValueError(
            f"{name} cannot be served: the engine's model protocol "
            f"requires {', '.join(missing)}")
    return module


def _split_step(out, n_pools: int):
    """A ``ragged_step``'s return value as (logits, the cache's arrays, what
    follows them: the routing record of a model with experts, or nothing)."""
    return out[0], tuple(out[1:1 + n_pools]), tuple(out[1 + n_pools:])


# ---------------------------------------------------------------------------
# Continuous batching (round 4): a fixed-slot serving loop
# ---------------------------------------------------------------------------
@dataclass
class _Request:
    rid: int
    prompt: np.ndarray
    tokens: list = field(default_factory=list)
    done: bool = False
    max_new_tokens: Optional[int] = None  # None -> engine config default
    trace_id: str = ""                    # serving-layer trace correlation
    sampler: Optional[SamplerConfig] = None   # None -> greedy row
    grammar: Any = None                   # TokenDFA; None -> unconstrained
    gstart: int = -1                      # arena GLOBAL start state
    gstate_host: int = -1                 # host DFA mirror (LOCAL ids)


class ContinuousBatchingEngine:
    """Fixed-slot continuous batching over the paged KV cache — the
    *service* engine the reference exposes through AnalysisPredictor's
    serving surface (paddle/fluid/inference/api/analysis_predictor.cc:§0;
    vLLM-style continuous batching over the paged pool, PAPERS.md ragged
    paged attention).

    ``num_slots`` sequences decode together in one compiled step; when a
    sequence hits EOS (or its token budget) its pages return to the pool
    and a queued request is prefilled INTO the freed slot while the other
    slots keep decoding. Admission control is host metadata only — device
    shapes (slots, page pool, block-table width) never change, so nothing
    recompiles at runtime.

    The unified ragged step: the WHOLE round — prefill chunks of newly
    admitted prompts, warm-prefix/COW suffixes and every decoding row —
    is ONE dispatch of one compiled program (the model module's
    ``ragged_step``, see ``_serving_module``, over
    ``ops.paged_attention.ragged_paged_attention``). Rows are metadata
    arrays padded to the fixed slot count, so the program's shape is
    invariant to the request mix: exactly one compile-cache entry ever
    (O(1) recompiles across a length-diverse storm), and a prompt
    submitted mid-decode joins the current step's batch immediately.

    Speculative decoding (``speculative=True``, default off): each
    decode row's round becomes ``[carry] + up to spec_k drafted
    tokens`` (inference/speculative.py — prompt-lookup self-drafting by
    default, ``DraftModel`` hook for a small draft model), verified by
    the SAME single-dispatch ragged program: the per-row last-token
    logits generalize to per-candidate logits, accept/reject is a
    host-side argmax comparison, and rejection rolls the paged pool
    back per row (``mgr.truncate_pages``). Greedy output stays
    byte-identical to non-speculative by construction
    (verify-then-commit); ``check_conservation`` runs after every
    speculative step.

    Host-fence discipline (every device->host value dependency stalls
    the dispatch pipeline): the ONLY transfer per round is the step's
    emitted tokens. Slot tokens live on device (the carry of the step's
    ``lax.scan``), each micro-round emits its INPUT token — so step
    outputs chain across steps without overlap and a finished prefill's
    first sample arrives with the row's first decode round — and
    positions are mirrored host-side analytically instead of being read
    back.

    Service API:
      ``submit(prompt) -> rid``; ``step(params)`` runs one admit+ragged
      round; ``collect()`` drains finished requests; ``serve(params,
      prompts)`` streams a whole list through the engine.
    """

    def __init__(self, model_config,
                 generation_config: Optional[GenerationConfig] = None,
                 num_slots: int = 8, page_size: int = 16,
                 max_seq_len: int = 2048, num_pages: Optional[int] = None,
                 chunk: int = 16, prefix_cache: bool = False,
                 check_invariants: bool = True,
                 step_tokens: Optional[int] = None,
                 speculative: bool = False, spec_k: int = 4,
                 drafter=None, fused_tail: bool = False,
                 mesh=None, mp_axis: str = "mp",
                 grammar_states: int = 0):
        from ..ops.paged_attention import (PagedKVCacheManager,
                                           kv_cache_layout)
        # a serving process counts its collector's pauses from its first
        # engine on (one hook however many engines; none at import)
        gc_collections.install()
        # the ONE place the engine learns which model it serves: the
        # config's class names the module that holds its step
        self._L = _serving_module(model_config)
        self.model_config = model_config
        self.config = generation_config or GenerationConfig()
        self.num_slots = num_slots
        self.page_size = page_size
        self.chunk = chunk
        self.max_seq_len = max_seq_len
        self._table_width = PagedKVCacheManager.pages_needed(max_seq_len,
                                                             page_size)
        # pool sized for every slot at max length unless told otherwise
        pool = num_pages or (num_slots * self._table_width + 1)
        mcfg = model_config
        # multi-chip TP serving (ROADMAP item 3): the weights are
        # Megatron-sharded and the paged pool head-sharded over the
        # mesh's mp axis — GQA groups mapped to chips. The unified
        # step's row metadata is shape-stable, so sharding is a LAYOUT
        # property of the arrays (device_put placements), not a new
        # program: the same single compiled step serves any degree and
        # O(1)-recompile behavior is untouched.
        self._mp_axis = mp_axis
        if mesh is not None and mp_axis not in mesh.shape:
            raise ValueError(
                f"serving mesh has no {mp_axis!r} axis (axes: "
                f"{tuple(mesh.shape)}) — build it with "
                "parallel.mesh.serving_mesh(...) or pass mp_axis naming "
                "the TP axis")
        chips = int(mesh.shape[mp_axis]) if mesh is not None else 1
        # a DEGREE-1 mesh is kept too: it carries no sharding but pins
        # the replica's device affinity — a replica resized down to one
        # chip must live on ITS surviving chip, not the process default
        # device another replica's mesh occupies
        self._mesh = mesh
        if self._mesh is not None:
            if chips > 1 and not any(
                    mp_axis in jax.tree_util.tree_leaves(tuple(spec))
                    for spec in self._L.serving_param_specs(mcfg).values()):
                raise ValueError(
                    f"{self._L.__name__} replicates every weight: it has "
                    f"no tensor-parallel layout for a mesh of degree "
                    f"{chips} over {mp_axis!r}; serve it on one chip")
            if mcfg.num_attention_heads % chips:
                raise ValueError(
                    f"TP degree {chips} must divide num_attention_heads="
                    f"{mcfg.num_attention_heads} (whole GQA groups per "
                    "chip — pick a degree via mesh.surviving_mp_degree)")
        # what a token keeps in the cache is the model's: K and V with a
        # head axis unless its module names another layout (which also
        # says whether a mesh can split it). The pool is allocated ON the
        # mesh (head-sharded from its first byte): a pool sized for the
        # mesh need not fit one chip
        layout = getattr(self._L, "cache_layout", None)
        layout = (layout(mcfg) if layout is not None else kv_cache_layout(
            mcfg.num_key_value_heads, mcfg.head_dim))
        # pages for the CACHE layers: one a model layer unless the layout
        # names another count (fewer where only some layers attend, more
        # where a layer attends twice)
        page_layers = layout.layers or mcfg.num_hidden_layers
        pool_args = (page_layers, pool, page_size)
        pool_kw = dict(dtype=mcfg.dtype, mesh=mesh, mp_axis=mp_axis,
                       layout=layout)
        # a model with recurrent layers also keeps a fixed-size state a
        # ROW (kvcache/state.py): owned by the slot, carried through the
        # step beside the pages. A prefix hit shares pages but not the
        # state at that boundary, and a rejected draft rolls pages back
        # but not a state: refused until the pool keeps snapshots
        state_layout = getattr(self._L, "state_layout", None)
        if state_layout is not None:
            for on, option in ((prefix_cache, "prefix_cache"),
                               (speculative, "speculative")):
                if on:
                    raise ValueError(
                        f"{self._L.__name__} keeps a recurrent state a "
                        f"row: {option}=True needs state snapshots, which "
                        "the state pool does not keep")
        if prefix_cache:
            # shared-ownership pool + radix prefix index: retired prompts
            # stay resident and later requests prefill only their suffix
            from ..kvcache import PrefixCache, RefcountedKVCacheManager
            self.mgr = RefcountedKVCacheManager(*pool_args, **pool_kw)
            self.cache: Optional["PrefixCache"] = PrefixCache(self.mgr)
        else:
            self.mgr = PagedKVCacheManager(*pool_args, **pool_kw)
            self.cache = None
        # one-slot param-placement cache: the caller keeps passing the
        # SAME host/replicated params object to step(); the engine
        # shards it onto ITS mesh once (each replica owns its own mesh
        # after an elastic resize, so placement must be per-engine). The
        # original params are held strongly so a recycled id() can never
        # alias a dead pytree.
        self._placed_params: Tuple = (None, None)
        # the conservation audit is O(pool) host work per step; on by
        # default (it anchors the shared-ownership model, and speculative
        # draft growth/rollback is the first path that returns pages
        # mid-sequence) but opt-out for latency-critical deployments
        # with very large pools
        self._check_invariants = check_invariants and (prefix_cache
                                                       or speculative)
        # host slot state
        self._slot_rid = [None] * num_slots       # rid occupying each slot
        if state_layout is not None:
            from ..kvcache.state import RowStatePool
            # the manager audits and reports it with the pages
            self.mgr.state = RowStatePool(state_layout(mcfg), num_slots,
                                          owners=self._slot_rid)
        self._queue: list = []                    # pending _Request
        self._live: Dict[int, _Request] = {}      # rid -> request (slotted)
        self._finished: Dict[int, list] = {}
        self._finished_crc: Dict[int, int] = {}  # rid -> crc32 of the
        # retired output, stamped in _retire — the engine-side checksum
        # the postmortem journal pairs against the router's stream crc
        self._next_rid = 0
        # slot tokens stay ON DEVICE (no per-admit readback); positions
        # are host-mirrored analytically
        self._tok_dev = jnp.zeros((num_slots,), jnp.int32)
        self._pos = np.zeros((num_slots,), np.int32)
        self._bt = np.zeros((num_slots, self._table_width), np.int32)
        # per-row sampling epilogue state (inference/sampling.py): the
        # (seeds, temps, top_k, top_p) HOST mirrors admission writes,
        # like ``_pos`` and ``_bt``; the device copies the step program
        # takes are refreshed with the plan's uploads, on a step whose
        # admission changed a value (``_upload_row_state``). Defaults are
        # greedy — a slot never inherits a retired request's temperature.
        self._samp = _sampling.init_row_state(num_slots)
        self._samp_dev = self._upload_copies(self._samp)
        self._samp_dirty = False
        # per-row grammar DFA state; -1 = unconstrained (mask is a no-op).
        # A carry the program advances, so admission cannot overwrite it
        # from a mirror: it writes the row's start state into ``_greset``
        # (``KEEP`` elsewhere), uploaded with the plan and applied at the
        # top of the program (constrain.reset_states). ``_gheld`` marks
        # the slots whose carry can hold anything but -1, so an
        # unconstrained request after an unconstrained one writes nothing.
        self._gstate_dev = jnp.full((num_slots,), -1, jnp.int32)
        self._greset = np.full((num_slots,), _constrain.KEEP, np.int32)
        self._greset_keep_dev, = self._upload_copies([self._greset])
        self._greset_dirty = False
        self._gheld = np.zeros((num_slots,), bool)
        # the grammar arena is ALLOCATED AT CONSTRUCTION with a fixed
        # shape — it is a program input, so sizing it lazily would
        # change the compiled signature and recompile. grammar_states=0
        # keeps a 1-row placeholder (constrained submit then raises with
        # the sizing hint); size it for the grammars you will serve
        # (json_grammar(max_depth=2) on a byte-ish vocab needs ~650).
        self._arena = _constrain.GrammarArena(
            mcfg.vocab_size, capacity_states=max(1, int(grammar_states)))
        # sampling epilogue compiled LAZILY: until the first
        # ``sampler=``/``grammar=`` submit the step programs trace the
        # argmax-only twins (sampling.greedy_rows/spec_greedy_rows) —
        # byte-identical greedy output at the pre-sampling compile
        # cost. The first such submit flips this STICKY flag and drops
        # the compiled programs: ONE counted recompile (the flag is in
        # the recompile key), after which mixed greedy/sampled/
        # constrained storms still run O(1) programs.
        self._epilogue_on = False
        # unified ragged step: ONE compiled program serving mixed
        # prefill+decode rows; its shape depends only on (slots, chunk,
        # step_tokens, table width) fixed at construction — O(1)
        # recompiles by design
        self._step_tokens = max(step_tokens or
                                max(num_slots, chunk, page_size), num_slots)
        self._unified_step = None
        self._unified_flags = None      # host state baked into the program
        # profile-guided fusion (jit/fusion.py decode_tail region,
        # default OFF): the step program is built by the fused builders
        # — identical compute graph fed from a PACKED two-upload plan,
        # the spec verify epilogue moves in-program, and steady-state
        # all-decode rounds plan through a vectorized fast path. Tokens
        # are byte-identical fused on/off; the admission gate lives in
        # benchmarks/bench_fusion.py.
        self._fused_tail = bool(fused_tail)
        self._pend = [None] * num_slots   # per-slot unfed prompt suffix
        # coalesced per-slot span windows ([kind, t0_ns, t1_ns, units]):
        # armed steps MERGE each slot's prefill/decode activity into one
        # growing window instead of emitting a span per step, flushed on
        # phase change and at retire/cancel — per-step armed cost is a
        # few list ops, inside bench_obs_overhead's budget. The emitted
        # decode span therefore covers the request's whole decode wall
        # time (host gaps between dispatches included), which is exactly
        # the "decode" segment the timeline attributes.
        self._win = [None] * num_slots
        # speculative decoding (inference/speculative.py): each decode
        # row's round becomes [carry + up to spec_k drafted tokens] — a
        # short prefill the same ragged program verifies in ONE dispatch
        # whose per-candidate argmax IS the accept/reject oracle.
        # Default OFF: the non-speculative step is byte-for-byte
        # untouched.
        self._speculative = bool(speculative)
        self.spec_k = int(spec_k)
        self.spec = None                # SpeculationTelemetry when enabled
        self.drafter = drafter
        self._spec_step = None
        self._spec_flags = None
        if speculative:
            # sampling composes with speculation since the rejection-
            # sampling verifier (sampling.spec_sample_rows) landed:
            # greedy rows keep verify-by-argmax byte-identity, sampled
            # rows accept draft j with prob p_target(d_j) and resample
            # the residual — distribution-identical to the non-spec
            # sampler (tests/test_sampling.py property test)
            from .speculative import NgramDrafter, SpeculationTelemetry
            self.drafter = drafter or NgramDrafter()
            self.spec = SpeculationTelemetry()
            # packed axis: every slot may speculate (1 carry + spec_k
            # drafts) in the same round; prefill shares what's left
            self._spec_tokens = max(self._step_tokens,
                                    num_slots * (self.spec_k + 1))
            # admission's page reservation per slot: rollback never
            # truncates below it (it is the row's guarantee that
            # committed decode can't OOM mid-flight)
            self._reserved = np.zeros((num_slots,), np.int64)
        #: prompt tokens actually run through prefill (cache hits skip
        #: their cached prefix; benchmarks diff this against submitted
        #: prompt lengths for the skip ratio)
        self._prefill_tokens = 0
        #: prompt tokens the prefix cache already held for the requests the
        #: last admission brought in (``cbe.upload``'s ``cached_tokens``)
        self._admitted_cached = 0
        #: unified dispatches since the engine was built: the ``n`` of the
        #: work record that rides on each ``cbe.dispatch`` span
        self._dispatches = 0
        #: per layer the attention's sliding window, None where it is full
        #: (what the work record counts the ragged kernel's pages by), and
        #: how many layers have each
        windows = getattr(self._L, "attention_windows", None)
        self._layer_windows = (
            tuple(windows(mcfg)) if windows is not None
            else (None,) * page_layers)
        self._window_layers = tuple(
            collections.Counter(self._layer_windows).items())
        # HBM memory ledger (observability/memory.py): when armed, every
        # step feeds the pool's byte split + per-request holdings and
        # runs the byte conservation audit alongside check_conservation.
        self._mem_tick = 0
        # serving-layer hooks (paddle_tpu.serving): both default to None so
        # the plain submit/step/collect/serve surface is byte-identical.
        # token_callback(rid, token) fires for every KEPT token as step()
        # unpacks a chunk; finish_callback(rid, tokens) fires at _retire.
        self.token_callback: Optional[Callable[[int, int], None]] = None
        self.finish_callback: Optional[Callable[[int, list], None]] = None

    # -- service API --------------------------------------------------------

    def _budget(self, req: "_Request") -> int:
        """Per-request new-token budget (submit() override or config)."""
        return (req.max_new_tokens if req.max_new_tokens is not None
                else self.config.max_new_tokens)

    @property
    def num_chips(self) -> int:
        """TP chips this engine is sharded over (1 = single-chip)."""
        return self.mgr.mesh_chips

    @property
    def mesh(self):
        """The serving TP mesh (None when single-chip)."""
        return self._mesh

    def _place_params(self, params):
        """Shard the caller's params onto this engine's mesh (cached by
        object identity — the serving loop passes one params object
        forever; a fresh object, e.g. after a weight swap, re-places)."""
        if self._placed_params[0] is params:
            return self._placed_params[1]
        placed = self._L.shard_params_tp(params, self._mesh,
                                         self.model_config)
        self._placed_params = (params, placed)
        return placed

    @property
    def num_free_slots(self) -> int:
        """Slots not occupied by a live sequence (pending queue not counted)."""
        return self._slot_rid.count(None)

    @property
    def num_queued(self) -> int:
        """Submitted requests waiting in the engine's internal FIFO (not
        yet holding a slot). The scheduler's admission headroom math uses
        this instead of reaching into ``._queue`` (tpu-lint
        private-engine)."""
        return len(self._queue)

    def submit(self, prompt, max_new_tokens: Optional[int] = None,
               trace_id: str = "", sampler: Optional[SamplerConfig] = None,
               grammar=None, grammar_prefix=None) -> int:
        """Queue a request. ``sampler`` carries the per-request
        temperature/top-k/top-p/seed (None follows the engine's
        ``GenerationConfig``: a per-request sampler derived from it when
        ``do_sample``, plain greedy otherwise); ``grammar`` is a
        ``constrain.TokenDFA`` constraining every generated token;
        ``grammar_prefix`` pre-advances the DFA through tokens this
        request already generated elsewhere (the router's failover
        resume, whose continuation prompt contains them). Both ride the
        unified step's in-program epilogue, so a mixed
        greedy/sampled/constrained batch stays ONE dispatch of ONE
        compiled program."""
        budget = (max_new_tokens if max_new_tokens is not None
                  else self.config.max_new_tokens)
        prompt = np.asarray(prompt, np.int32)
        if len(prompt) + budget > self.max_seq_len:
            raise ValueError(
                f"prompt of {len(prompt)} tokens + max_new_tokens="
                f"{budget} exceeds the engine's "
                f"max_seq_len={self.max_seq_len}; raise max_seq_len or "
                "truncate the prompt (silent page clamping would corrupt "
                "the sequence's KV)")
        rid = self._next_rid
        self._next_rid += 1
        if sampler is None and self.config.do_sample:
            # engine-wide do_sample maps onto the same per-request
            # epilogue: one derived SamplerConfig per request, seeded
            # from (config seed, rid) so streams are replayable
            sampler = SamplerConfig(temperature=self.config.temperature,
                                    top_k=self.config.top_k,
                                    top_p=self.config.top_p)
        if sampler is not None:
            sampler = sampler.resolved(
                self.config.seed * 1000003 + 7919 * rid)
        if (sampler is not None or grammar is not None) \
                and not self._epilogue_on:
            # first sampled/constrained request: swap the argmax-only
            # tail for the full in-program epilogue — ONE counted
            # recompile (the flag is in the recompile key), sticky for
            # the engine's lifetime
            self._epilogue_on = True
            self._unified_step = None
            self._spec_step = None
        gstart, ghost = -1, -1
        if grammar is not None:
            # ValueError on vocab mismatch / arena overflow — at submit,
            # never mid-step
            gstart = self._arena.register(grammar)
            _sampling.set_grammar_states(self._arena.used)
            ghost = grammar.start
            for t in (grammar_prefix if grammar_prefix is not None
                      else ()):
                ghost = grammar.advance(ghost, int(t))
                if ghost == _constrain.ILLEGAL:
                    raise ValueError(
                        f"grammar_prefix token {int(t)} is illegal in "
                        f"grammar {grammar.pattern!r} — the resumed "
                        "stream cannot have produced it")
            _sampling.note_request("constrained")
        elif sampler is not None and sampler.temperature > 0:
            _sampling.note_request("sampled")
        self._queue.append(_Request(rid, prompt,
                                    max_new_tokens=max_new_tokens,
                                    trace_id=trace_id, sampler=sampler,
                                    grammar=grammar, gstart=gstart,
                                    gstate_host=ghost))
        return rid

    def cancel(self, rid: int) -> bool:
        """Abort a request mid-flight. Queued: dropped before admission.
        Live: the slot is retired immediately — pages return to the pool,
        the block-table row points back at the garbage page, and nothing
        lands in the finished map (the caller initiated the abort, so no
        finish_callback fires either). Returns False for unknown/done rids.
        """
        for i, req in enumerate(self._queue):
            if req.rid == rid:
                self._queue.pop(i)
                return True
        if rid in self._live:
            self._retire(self._slot_rid.index(rid), cancelled=True)
            return True
        return False

    def _admit_pick(self):
        """Shared admission bookkeeping (host metadata only): pop queued
        requests into free slots, resolve the prefix cache (shared pages,
        COW copy), allocate pages. Returns the picked
        ``(slot, req, pages_row, prompt_len, n_cached)`` list; the step
        queues each pick's suffix tokens into the next ragged round."""
        picked = []                # (slot, req, pages_row, lp, n_cached)
        recorded = []              # deferred stats-only cache accounting
        try:
            picked = self._admit_window(picked, recorded)
            for req, r_lp, r_cached, r_shared, r_cow in recorded:
                # stats-only lookup accounting (counters + cache_hit
                # event), deferred until the WHOLE window lands so a
                # mid-window raise can't count a hit for a request that
                # gets rolled back and re-admitted next step
                try:
                    self.cache.record(req.rid, r_lp, r_cached, r_shared,
                                      cow=r_cow is not None,
                                      trace_id=req.trace_id)
                except Exception:
                    # a broken stats sink must not tear down an admitted
                    # window (events.emit discipline): rolling back here
                    # would re-admit and DOUBLE-count the hits already
                    # recorded — undercounting once is the safe failure
                    pass
        except BaseException:
            # admission is atomic across the whole window: requests are
            # admitted only once every picked entry lands, so anything
            # raising between an allocate and the return must free EVERY
            # picked allocation and requeue the requests at the head in
            # original order — rolling back only the current request
            # would orphan earlier picks: their pages leak (never reach
            # _slot_rid, so cancel/retire can't find them) and the
            # requests silently vanish (tpu-lint page-leak)
            for _, req, _, _, _ in reversed(picked):
                self.mgr.free(req.rid)
                self._queue.insert(0, req)
            raise
        return picked

    def _admit_window(self, picked, recorded):
        for s in range(self.num_slots):
            if self._slot_rid[s] is not None or not self._queue:
                continue
            req = self._queue[0]
            lp = len(req.prompt)
            total = lp + self._budget(req)       # submit() bounds this
            shared: list = []
            n_cached = 0
            cow_src = None
            if self.cache is not None:
                shared, n_cached, cow_src = self.cache.lookup(req.prompt)
            need_fresh = self.mgr.pages_for(total) - len(shared)
            if self.mgr.num_free_pages < need_fresh and self.cache is not None:
                # reclaim cold cached pages before deferring admission;
                # protect the pages THIS lookup is about to share (their
                # refcounts rise only at allocate)
                self.cache.evict(need_fresh - self.mgr.num_free_pages,
                                 protect=shared + [cow_src])
                if (self.mgr.num_free_pages < need_fresh
                        and cow_src is not None):
                    # still short: give up the COW page (one more
                    # evictable) and recompute its block instead
                    cow_src, n_cached = None, len(shared) * self.page_size
                    self.cache.evict(
                        need_fresh - self.mgr.num_free_pages,
                        protect=shared)
            if self.mgr.num_free_pages < need_fresh:
                if not self._live and not picked:
                    # infeasibility is judged against WHOLE-pool capacity:
                    # with nothing live and nothing evictable left, a
                    # request within capacity admits (free == usable -
                    # shared); beyond capacity nothing ever will
                    if self.mgr.pages_for(total) > self.mgr.usable_pages:
                        memory_ledger.note_oom(
                            "infeasible", self.mgr,
                            need_pages=self.mgr.pages_for(total),
                            free_pages=self.mgr.num_free_pages,
                            request_id=req.rid, trace_id=req.trace_id)
                        raise MemoryError(
                            f"request {req.rid} needs "
                            f"{self.mgr.pages_for(total)} pages but the "
                            f"pool only holds {self.mgr.usable_pages}; "
                            "enlarge num_pages")
                break                    # pool full: wait for a completion
            if self.cache is not None:
                pages = self.mgr.allocate(req.rid, total, shared=shared)
            else:
                pages = self.mgr.allocate(req.rid, total)
            # ownership transfers into ``picked`` IMMEDIATELY (the
            # rollback in _admit_pick owns the pages from here); the
            # pop comes after, so an allocate raise leaves the request
            # queued with nothing to undo
            picked.append((s, req, pages, lp, n_cached))
            self._queue.pop(0)
            if self.cache is not None and cow_src is not None:
                # the suffix's first write lands mid-page: append into
                # a private device-side copy, never the shared page
                self.mgr.copy_page(cow_src, pages[len(shared)])
            self.mgr._lens[req.rid] = lp
            if memory_armed[0]:
                # per-request HBM attribution: cached-vs-fresh page
                # split for /memz, memory.json and the request span args
                memory_ledger.note_request(
                    self.mgr, req.rid, prompt_len=lp,
                    cached_pages=len(shared), trace_id=req.trace_id)
            if self.cache is not None:
                recorded.append((req, lp, n_cached, len(shared), cow_src))
        return picked

    def _complete(self, req) -> bool:
        cfg = self.config
        if len(req.tokens) >= self._budget(req):
            return True
        return (cfg.eos_token_id is not None
                and req.tokens and req.tokens[-1] == cfg.eos_token_id)

    def _note_win(self, s, kind: str, t0_ns: int, t1_ns: int, units: int,
                  batch: list) -> None:
        """Merge one armed step's activity into the slot's pending span
        window (same kind: extend + accumulate; phase change: flush the
        old window into ``batch`` and start a new one)."""
        w = self._win[s]
        if w is not None:
            if w[0] == kind:
                w[2] = t1_ns
                w[3] += units
                return
            self._flush_win(s, batch)
        self._win[s] = [kind, t0_ns, t1_ns, units]

    def _flush_win(self, s, batch: Optional[list] = None) -> None:
        """Emit the slot's pending coalesced span (no-op when none). A
        cancel flushes too — a mid-decode failover must not lose the
        dead replica's decode segment from the request's trace."""
        w = self._win[s]
        if w is None:
            return
        self._win[s] = None
        rid = self._slot_rid[s]
        req = self._live.get(rid)
        if req is None:
            return
        kind, t0_ns, t1_ns, units = w
        if kind == "prefill":
            sp = make_span("engine.prefill", t0_ns, t1_ns, "Operator",
                           req.trace_id,
                           args={"request_id": rid, "slot": s,
                                 "prefill_tokens": units})
        else:
            sp = make_span("engine.decode_chunk", t0_ns, t1_ns,
                           "Operator", req.trace_id,
                           args={"request_id": rid, "slot": s,
                                 "chunk": units})
        if batch is None:
            emit_spans([sp])
        else:
            batch.append(sp)

    def _retire(self, s, cancelled: bool = False):
        """Free a finished (or cancelled) slot: pages back to the pool,
        output to the finished map, slot table pointed at the reserved
        garbage page. Cancelled slots free resources but produce no
        finished entry and no finish_callback."""
        self._flush_win(s)
        rid = self._slot_rid[s]
        req = self._live.pop(rid)
        req.done = True
        if not cancelled:
            out = req.tokens[:self._budget(req)]
            self._finished[rid] = out
            self._finished_crc[rid] = token_checksum(out)
            if self.cache is not None:
                # index the finished prefix BEFORE release: pages backing
                # its full token blocks stay resident (refcount 0, cached)
                # instead of draining to the free list. Positions past the
                # kept output may hold over-decoded garbage, but those
                # never complete a block (full blocks end <= kept length).
                # The sequence goes to the index as ONE int32 array: the
                # tree reads it once a walk, so the span holds a copy of
                # the tokens and the walk, and no per-token conversion.
                with phase("paddle_serving.prefix_insert") as span:
                    toks = np.concatenate(
                        [req.prompt, np.asarray(out, np.int32)])
                    if self._speculative and out:
                        # the last delivered token may be the verify bonus
                        # — committed but never fed back, so its K/V slot
                        # was never written. Index one token short so a
                        # future cache hit can never attend a hole.
                        toks = toks[:-1]
                    adopted = self.cache.insert(toks, self.mgr._tables[rid])
                    span.set_metadata(tokens=len(toks), pages=adopted)
            if self.finish_callback is not None:
                self.finish_callback(rid, out)
        self.mgr.free(rid)
        self._slot_rid[s] = None
        self._bt[s] = 0
        self._pos[s] = 0
        self._pend[s] = None

    def _deliver_tokens(self, s, tokens) -> bool:
        """Unpack one slot's emitted tokens: append to the request, fire
        ``token_callback`` per token (surviving a reentrant in-place
        cancel from inside the callback), retire on completion. Shared
        verbatim by the unified and speculative steps — the reentrancy
        contract must never fork. Returns True while the slot's request
        keeps decoding (caller may advance its position mirror)."""
        rid = self._slot_rid[s]
        req = self._live[rid]
        mode = ("constrained" if req.grammar is not None else
                "sampled" if (req.sampler is not None
                              and req.sampler.temperature > 0) else None)
        for t in tokens:
            t = int(t)
            if req.grammar is not None:
                # host DFA mirror: the audit half of constrained
                # decoding (the mask is the mechanism). Device and host
                # walk the same table, so a disagreement means real
                # corruption — count it, emit the event, keep serving.
                if req.grammar.legal(req.gstate_host, t):
                    req.gstate_host = req.grammar.advance(
                        req.gstate_host, t)
                else:
                    _sampling.note_violation()
                    emit_event("constraint_violation", request_id=rid,
                               trace_id=req.trace_id, token=t,
                               state=int(req.gstate_host),
                               pattern=req.grammar.pattern)
            if mode is not None:
                _sampling.note_tokens(mode, 1)
            req.tokens.append(t)
            if self.token_callback is not None:
                self.token_callback(rid, t)
                if self._slot_rid[s] != rid:
                    return False   # callback cancelled this request
            if self._complete(req):
                break
        if self._slot_rid[s] != rid:
            return False           # already retired by a reentrant cancel
        if self._complete(req):
            self._retire(s)
            return False
        return True

    def step(self, params) -> int:
        """One admit + decode round (ONE device->host transfer: the
        step's emitted tokens). Returns the live count after the round.

        Admission is host bookkeeping only and the round is ONE ragged
        dispatch — newly admitted prompts join the current step's packed
        batch immediately, alongside every decoding row. Speculative
        mode folds draft verification into the same single dispatch
        (``_step_spec``)."""
        with phase("cbe.step"):
            if self._mesh is not None:
                params = self._place_params(params)
            if self._speculative:
                n = self._step_spec(params)
            else:
                n = self._step_unified(params)
            if memory_armed[0]:
                # the memory half of the per-step audit: byte split by
                # class + per-request holdings + byte conservation, run
                # alongside check_conservation (one list index when
                # disarmed)
                self._note_memory(params)
        return n

    def _note_memory(self, params) -> None:
        """Feed the HBM ledger one accounting round (armed only): model
        weights (once per params object), the pool's page split with the
        speculative-tail attribution, and the prefix-cache stats.

        Invariant-checked engines feed EVERY step — the byte
        conservation audit rides alongside ``check_conservation``. An
        engine that opted out of per-step invariant checking (the
        latency-critical large-pool configuration) decimates its feed
        to every 16th step: the common armed-step cost collapses to one
        counter bump, and the books refresh on that cadence instead."""
        if not self._check_invariants:
            self._mem_tick += 1
            if self._mem_tick & 15:
                return
        # cheap on the cached path (identity + fingerprint dict hit);
        # the ledger itself guards against id reuse across dead pytrees
        memory_ledger.note_weights(params)
        reserved = None
        if self._speculative:
            reserved = {self._slot_rid[s]: int(self._reserved[s])
                        for s in range(self.num_slots)
                        if self._slot_rid[s] is not None}
        memory_ledger.observe(
            self.mgr, reserved=reserved,
            cache_stats=self.cache.stats if self.cache is not None
            else None,
            audit=self._check_invariants)

    # -- unified ragged step (the serving path) ------------------------------

    def _admit(self) -> int:
        """Admission, shared by both step paths: host bookkeeping only
        (no device computation, no transfer — what it writes rides the
        step's uploads). Returns the number of requests admitted, and
        leaves in ``_admitted_cached`` how many of their prompt tokens the
        prefix cache already held."""
        picked = self._admit_pick()
        for s, req, pages, lp, nc in picked:
            self._slot_rid[s] = req.rid
            self._live[req.rid] = req
            self._pos[s] = nc                 # next position to write
            self._bt[s] = 0
            self._bt[s, :len(pages)] = pages
            # a warm/COW suffix row IS "a row whose first position >
            # 0"; cold rows just start at 0 — one code path
            self._pend[s] = np.asarray(req.prompt[nc:], np.int32)
            if self._speculative:
                self._reserved[s] = len(pages)
            self._set_row_sampler(s, req)
        self._admitted_cached = sum(nc for *_, nc in picked)
        return len(picked)

    def _set_row_sampler(self, s: int, req: "_Request") -> None:
        """Write one admitted request's sampler/grammar parameters into
        the per-row host mirrors (numpy, like ``_pos`` and ``_bt``).
        ALWAYS runs — a greedy request resets the slot, so reuse never
        inherits a retired row's temperature or a stale grammar state —
        and marks a mirror dirty only where a value changed: greedy
        after greedy, unconstrained after unconstrained cost the device
        nothing."""
        if _sampling.set_row(self._samp, s, req.sampler):
            self._samp_dirty = True
        g = -1
        if req.grammar is not None:
            # arena rows are the grammar block rebased by its offset:
            # global = (gstart - local start) + local host-mirror state
            g = req.gstart - req.grammar.start + req.gstate_host
        if g != -1 or self._gheld[s]:
            self._greset[s] = g
            self._greset_dirty = True
            self._gheld[s] = g != -1

    def _upload_row_state(self):
        """Refresh the device copies of the per-row mirrors admission
        changed (one transfer an array, with the plan's own uploads) and
        return the step's grammar reset: the cached all-``KEEP`` array
        unless a row restarts. A step whose admission changed nothing
        transfers nothing here."""
        if self._samp_dirty:
            self._samp_dev = self._upload_copies(self._samp)
            self._samp_dirty = False
        if not self._greset_dirty:
            return self._greset_keep_dev
        greset, = self._upload_copies([self._greset])
        self._greset[:] = _constrain.KEEP
        self._greset_dirty = False
        return greset

    @staticmethod
    def _upload_copies(mirrors) -> Tuple:
        """Device arrays of PRIVATE copies of host mirrors that later
        admissions write in place. A transfer may still be reading its
        source when this returns, or alias it outright (the CPU backend,
        an aligned buffer), with ``jnp.asarray`` and ``jnp.array`` alike:
        a mirror handed over itself would leak its next write into the
        device array."""
        return tuple(jnp.asarray(a.copy()) for a in mirrors)

    def _epilogue_active(self) -> bool:
        """Any live request exercising the sampling epilogue (sampled or
        constrained rows) — gates the armed ``cbe.sample_epilogue``
        profiling tap."""
        return any(r.sampler is not None or r.grammar is not None
                   for r in self._live.values())

    def enable_fused_tail(self) -> "ContinuousBatchingEngine":
        """Install the profile-guided decode-tail megaregion (the
        fusion pass's ``decode_tail`` region). Idempotent. Enabled
        before the first step it keeps the engine's ONE compile-cache
        miss; flipping mid-serve drops the compiled program and rebuilds
        on the next step — a counted miss, same contract as a baked-in
        flags flip."""
        if not self._fused_tail:
            self._fused_tail = True
            self._unified_step = None
            self._spec_step = None
        return self

    def _build_unified_step(self, mesh=None):
        """ONE compiled program for every step the engine will ever run:
        ``chunk`` micro-rounds of the ragged model step
        (models.llama.ragged_step) under one ``lax.scan``. Per micro-round
        every decoding row advances one token (its sampled carry feeds
        back in-program, so a chunk still costs one host round-trip) and
        prefilling rows consume the next span of their prompt from the
        host-planned packed layout. Shapes depend only on (slots, chunk,
        step_tokens, table width) — the request mix, prompt lengths and
        admission timing never recompile anything. ``mesh`` overrides
        the engine's own (``lower_unified_step`` only)."""
        L = self._L
        mcfg = self.model_config
        n_rows = self.num_slots
        mesh = mesh if mesh is not None else self._mesh
        mp_axis = self._mp_axis
        # lazy epilogue: until the first sampler/grammar submit the
        # program traces the argmax-only tail and no grammar mask —
        # the pre-sampling compute graph at the pre-sampling compile
        # cost (greedy output is byte-identical either way)
        epilogue = self._epilogue_on
        tail = _sampling.sample_rows if epilogue else _sampling.greedy_rows
        if self._fused_tail:
            # the fused decode-tail twin: SAME compute graph (the
            # builder receives the model step + sampling epilogue as
            # injected callables) fed from the packed plan —
            # byte-identical emitted tokens, one compile, two plan
            # uploads
            from ..jit import fusion as _fusion

            def model_step(params, ids, token_row, positions, kv_lens,
                           last_idx, pools, bt, gst, gtable):
                hook = (lambda lg: _constrain.mask_logits(
                    lg.astype(jnp.float32), gst, gtable)) \
                    if epilogue else None
                return _split_step(L.ragged_step(
                    params, ids, token_row, positions, kv_lens, last_idx,
                    *pools, bt, mcfg, mesh=mesh,
                    mp_axis=mp_axis, logits_epilogue=hook),
                    len(pools))[:2]

            return _fusion.build_fused_unified_step(
                model_step, tail, _constrain.reset_states, n_rows)

        def run(params, ids, use_carry, token_row, positions, kv_lens,
                last_idx, sample_mask, tok, gstate, greset, samp, gtable,
                pools, bt):
            gstate = _constrain.reset_states(gstate, greset)

            def micro(carry, xs):
                tok, gst, pools = carry
                ids_k, uc_k, tr_k, pos_k, kvl_k, li_k, sm_k = xs
                row_c = jnp.clip(tr_k, 0, n_rows - 1)
                # decode slots take the row's carry token (last sample);
                # prefill slots take the host-fed prompt tokens
                ids_eff = jnp.where(uc_k, jnp.take(tok, row_c), ids_k)
                # the grammar mask rides the model's logits-epilogue
                # hook: applied BEFORE the sampling epilogue so
                # constrained rows renormalize over legal tokens only
                # (an exact no-op for unconstrained rows — greedy
                # byte-identity)
                hook = (lambda lg: _constrain.mask_logits(
                    lg.astype(jnp.float32), gst, gtable)) \
                    if epilogue else None
                # a model with experts returns one more value, its small
                # int32 routing record: stacked over the micro-rounds and
                # handed out untouched (Llama returns none, and its
                # program is what it was)
                logits, pools, aux = _split_step(L.ragged_step(
                    params, ids_eff, tr_k, pos_k, kvl_k, li_k, *pools,
                    bt, mcfg, mesh=mesh, mp_axis=mp_axis,
                    logits_epilogue=hook), len(pools))
                # the in-program sampling epilogue (sampling.sample_rows):
                # per-row temperature/top-k/top-p + counter-based PRNG
                # keyed on the token's sequence position (= this round's
                # kv_len), greedy rows bit-exact argmax. No key threads
                # through the carry — the position IS the counter.
                nxt, ngst = tail(logits, kvl_k, samp, gst, gtable)
                # emit the INPUT carry: step outputs chain across steps
                # and a finished prefill's first sample arrives with the
                # row's first decode round
                emit = tok
                tok = jnp.where(sm_k, nxt, tok)
                gst = jnp.where(sm_k, ngst, gst)
                return (tok, gst, pools), (emit, *aux)

            (tok, gstate, pools), (toks, *aux) = jax.lax.scan(
                micro, (tok, gstate, pools),
                (ids, use_carry, token_row, positions, kv_lens, last_idx,
                 sample_mask))
            # toks (K, R); aux, if any, (K, ...)
            return (toks, tok, gstate, pools, *aux)

        return jax.jit(run, donate_argnums=(13,))

    def lower_unified_step(self, mesh=None):
        """``jax.stages.Lowered`` of the unified step at this engine's
        shapes and dense weights, traced over abstract inputs — nothing
        runs. The read-only window onto WHICH program the engine serves
        with: ``.as_text()`` shows whether the Pallas kernels are
        in it (``tpu_custom_call``) or gave way to their XLA references,
        ``.compile()`` is the ahead-of-time check. ``mesh`` stands in
        for the engine's own, e.g. one built over
        ``jax.experimental.topologies`` devices to compile for chips this
        process does not hold."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        mesh = mesh if mesh is not None else self._mesh
        mcfg = self.model_config
        K, tb, R = self.chunk, self._step_tokens, self.num_slots

        def abstract(shape, dtype, spec=P()):
            return jax.ShapeDtypeStruct(
                shape, dtype, sharding=None if mesh is None
                else NamedSharding(mesh, spec))

        specs = self._L.serving_param_specs(mcfg)
        params = {
            k: abstract(v.shape, v.dtype, specs[k]) for k, v in
            jax.eval_shape(
                lambda: self._L.init_stacked_params(mcfg)).items()}
        pools = tuple(
            abstract(p.shape, p.dtype, spec) for p, spec in zip(
                self.mgr.pools, self.mgr.layout.pool_specs(self._mp_axis)))
        pools += tuple(abstract(a.shape, a.dtype)
                       for a in self.mgr.arrays[len(pools):])
        if self._fused_tail:        # jit.fusion.pack_plan's two uploads
            plan = [abstract((4, K, tb), jnp.int32),
                    abstract((3, K, R), jnp.int32)]
        else:
            plan = [abstract((K, n), dt) for n, dt in (
                (tb, jnp.int32), (tb, jnp.bool_), (tb, jnp.int32),
                (tb, jnp.int32), (R, jnp.int32), (R, jnp.int32),
                (R, jnp.bool_))]
        table = self._arena.device_table()
        return self._build_unified_step(mesh).lower(
            params, *plan, abstract((R,), jnp.int32),
            abstract((R,), jnp.int32), abstract((R,), jnp.int32),
            jax.tree_util.tree_map(
                lambda a: abstract(a.shape, a.dtype), self._samp_dev),
            abstract(table.shape, table.dtype), pools,
            abstract((R, self._table_width), jnp.int32))

    def _plan_step(self):
        """Host-side layout of one unified step: simulate ``chunk``
        micro-rounds over the live slots, packing each round's tokens
        into the fixed ``step_tokens`` axis. Decode rows (no pending
        prompt) always claim one slot each; prefill rows share the
        remaining budget in slot order, transitioning to decode the
        round after their prompt completes. Returns the device metadata
        arrays plus host-only unpack masks; advances the slot mirrors
        (positions, pending suffixes)."""
        K, tb, n_rows = self.chunk, self._step_tokens, self.num_slots
        ids = np.zeros((K, tb), np.int32)
        use_carry = np.zeros((K, tb), bool)
        token_row = np.full((K, tb), -1, np.int32)
        positions = np.zeros((K, tb), np.int32)
        kv_lens = np.zeros((K, n_rows), np.int32)
        last_idx = np.zeros((K, n_rows), np.int32)
        sample_mask = np.zeros((K, n_rows), bool)
        emit = np.zeros((K, n_rows), bool)
        emit_counts = [0] * n_rows            # per-slot decode rounds
        fed = [0] * n_rows                    # prefill tokens consumed
        pos = self._pos.astype(np.int64).copy()
        rem = {s: len(self._pend[s]) for s in range(n_rows)
               if self._slot_rid[s] is not None and self._pend[s] is not None}
        for k in range(K):
            live = [s for s in range(n_rows)
                    if self._slot_rid[s] is not None]
            budget = tb - sum(1 for s in live if rem.get(s, 0) == 0)
            take = {}
            for s in live:
                if rem.get(s, 0) > 0:
                    take[s] = min(rem[s], budget)
                    budget -= take[s]
            cursor = 0
            for s in live:
                if rem.get(s, 0) > 0:          # prefilling
                    n = take[s]
                    if n == 0:
                        continue               # starved this round
                    sl = slice(cursor, cursor + n)
                    ids[k, sl] = self._pend[s][fed[s]:fed[s] + n]
                    token_row[k, sl] = s
                    positions[k, sl] = pos[s] + np.arange(n)
                    pos[s] += n
                    fed[s] += n
                    rem[s] -= n
                    last_idx[k, s] = cursor + n - 1
                    if rem[s] == 0:
                        # prompt complete: this round's last logits are
                        # the row's first sample (kept in the carry)
                        sample_mask[k, s] = True
                    cursor += n
                else:                          # decoding
                    use_carry[k, cursor] = True
                    token_row[k, cursor] = s
                    positions[k, cursor] = pos[s]
                    pos[s] += 1
                    last_idx[k, s] = cursor
                    sample_mask[k, s] = True
                    emit[k, s] = True
                    emit_counts[s] += 1
                    cursor += 1
                kv_lens[k, s] = pos[s]
        self._pos = pos.astype(np.int32)
        for s in list(rem):
            self._pend[s] = (None if rem[s] == 0
                             else self._pend[s][fed[s]:])
        return (ids, use_carry, token_row, positions, kv_lens, last_idx,
                sample_mask), emit, emit_counts, fed

    def _plan_step_packed(self):
        """Fused-tail planning: the same plan arrays as
        :meth:`_plan_step` packed into TWO int32 uploads
        (``jit.fusion.pack_plan``), with a vectorized fast path for the
        steady-state round where every live slot is decoding — the
        K×slots Python simulation collapses to a handful of numpy
        broadcasts (byte-equality with the generic planner is asserted
        in tests/test_fusion.py)."""
        from ..jit.fusion import pack_plan
        K, tb, n_rows = self.chunk, self._step_tokens, self.num_slots
        live = [s for s in range(n_rows)
                if self._slot_rid[s] is not None]
        if live and all(self._pend[s] is None for s in live):
            nl = len(live)
            lv = np.asarray(live, np.int64)
            ids = np.zeros((K, tb), np.int32)
            use_carry = np.zeros((K, tb), bool)
            use_carry[:, :nl] = True
            token_row = np.full((K, tb), -1, np.int32)
            token_row[:, :nl] = lv
            positions = np.zeros((K, tb), np.int32)
            base = self._pos[lv].astype(np.int64)
            k_col = np.arange(K, dtype=np.int64)[:, None]
            positions[:, :nl] = base[None, :] + k_col
            kv_lens = np.zeros((K, n_rows), np.int32)
            kv_lens[:, lv] = base[None, :] + k_col + 1
            last_idx = np.zeros((K, n_rows), np.int32)
            last_idx[:, lv] = np.arange(nl, dtype=np.int64)[None, :]
            sample_mask = np.zeros((K, n_rows), bool)
            sample_mask[:, lv] = True
            emit = np.zeros((K, n_rows), bool)
            emit[:, lv] = True
            self._pos[lv] = (base + K).astype(np.int32)
            emit_counts = [0] * n_rows
            for s in live:
                emit_counts[s] = K
            fed = [0] * n_rows
            plan = (ids, use_carry, token_row, positions, kv_lens,
                    last_idx, sample_mask)
        else:
            plan, emit, emit_counts, fed = self._plan_step()
        plan_tt, plan_tr = pack_plan(*plan)
        return plan_tt, plan_tr, emit, emit_counts, fed

    def _dispatch_record(self, token_row, positions, kv_lens, emit_counts,
                         fed) -> Dict[str, int]:
        """What one unified dispatch asks of the device, as integers from
        the plan arrays — they ride on the ``cbe.dispatch`` span into any
        profiler trace, where ``perfbench/program_trace.py`` reads the
        ragged kernel's block fill share and required bytes/FLOPs off them.
        ``attended_pages``, ``grid_steps`` and ``causal_pairs`` are the
        per-layer MEAN of what the dispatch's kernel calls walk (sum over
        layers / layers, rounded), so ``layers x`` them is the dispatch's
        total; where all layers are alike, as Llama's, it is one layer's
        count. Per layer: the kernel's grid walks each micro-round's live
        blocks (``ops.paged_attention.ragged_live_blocks``: up to G =
        ``ragged_block_pages`` consecutive pages of one row a step; a
        starved row's ``kv_lens`` is 0) and takes one step in a round that
        has none. ``grid_steps`` counts the PAGE SLOTS those steps hold, G a
        block, and ``attended_pages`` of them have a live page
        (``ragged_live_pages``): their ratio is how full the blocks run,
        100% only where every row's page count is a multiple of G.
        ``causal_pairs`` query-key pairs pass the mask. A model with
        sliding-window layers (its serving module's ``attention_windows``)
        adds ``window_skipped_pages``: live pages a full mask would have
        listed and the window did not, the same mean. A model whose pools
        have no head axis (the latent kernel's walk) adds ``shared_pages``:
        the attended pages that its calls fold under ANOTHER row's work item,
        because both rows' block tables name them (a borrowed prefix;
        ``ragged_shared_blocks``), the same mean; what each ROW attends
        (``attended_pages``, ``grid_steps``, ``causal_pairs``) counts them
        all the same. A model with a state a row (``state_layout``) adds
        ``state_row_rounds``, ``state_resets`` and ``state_bytes_per_row``
        (at the end).
        Computed on every dispatch (a few vectorised numpy lines)."""
        from ..ops.paged_attention import (ragged_block_pages,
                                           ragged_first_pages,
                                           ragged_live_blocks,
                                           ragged_live_pages,
                                           ragged_shared_blocks)
        ps = self.page_size
        width = self._table_width
        group = ragged_block_pages(ps, width)
        full_pages = ragged_live_pages(kv_lens, ps, width)
        seen = (positions + 1)[token_row >= 0]
        # per kind of layer (a window, or None): live pages and blocks of
        # each micro-round and the pairs the mask lets through, weighted by
        # how many layers are of that kind
        attended = steps = pairs = 0
        for window, share in self._window_layers:
            first = None if window is None else ragged_first_pages(
                token_row, positions, self.num_slots, ps, window)
            pages = full_pages if window is None else ragged_live_pages(
                kv_lens, ps, width, first)
            blocks = ragged_live_blocks(kv_lens, ps, width, first)
            attended += share * int(pages.sum())
            steps += share * group * int(np.maximum(blocks, 1).sum())
            pairs += share * int((seen if window is None
                                  else np.minimum(seen, window)).sum())
        layers = len(self._layer_windows)
        n = self._dispatches
        self._dispatches = n + 1
        record = {
            "n": n,
            "rounds": self.chunk,
            "token_slots": self.chunk * self._step_tokens,
            "prefill_tokens": sum(fed),
            "decode_tokens": sum(emit_counts),
            "live_rows": self.num_slots - self._slot_rid.count(None),
            "attended_pages": round(attended / layers),
            "grid_steps": round(steps / layers),
            "causal_pairs": round(pairs / layers),
            "page_size": ps,
        }
        if len(self._window_layers) > 1 or self._layer_windows[0] is not None:
            record["window_skipped_pages"] = round(
                int(full_pages.sum()) - attended / layers)
        if self.mgr.layout.head_axis is None:
            # every layer's call sees the same table and spans
            record["shared_pages"] = group * int(
                ragged_shared_blocks(self._bt, kv_lens, ps).sum())
        state = self.mgr.state
        if state is not None:
            # a model with a state a row: the rows whose state each
            # micro-round advanced (a row with a token that round), summed
            # over the rounds; the rows that started from zeros (their first
            # token at position 0); one row's state, one layer, as counted
            worked = np.zeros((self.chunk, self.num_slots + 1), bool)
            worked[np.arange(self.chunk)[:, None], token_row] = True
            record["state_row_rounds"] = int(worked[:, :-1].sum())
            record["state_resets"] = int(
                ((positions == 0) & (token_row >= 0)).sum())
            record["state_bytes_per_row"] = state.layout.row_layer_nbytes
        return record

    @staticmethod
    def _expert_stats(aux) -> Dict[str, int]:
        """The ``cbe.unpack`` span's integer stats from a dispatch's routing
        record ``aux`` (rounds, expert layers, 3: experts hit, largest
        expert load, assignments; ``ops.moe_ops.grouped_expert_ffn``): sums
        over the dispatch's ``expert_calls`` = rounds x expert layers, all
        four among the experts HELD. A model whose router has zero-compute
        experts records 5 numbers a call: the assignments that chose one
        and the router's (valid tokens x k) ride along as two more."""
        stats = {"experts_hit": int(aux[..., 0].sum()),
                 "expert_calls": int(aux[..., 0].size),
                 "expert_assignments": int(aux[..., 2].sum()),
                 "max_expert_load": int(aux[..., 1].sum())}
        if aux.shape[-1] > 3:
            stats["zero_expert_assignments"] = int(aux[..., 3].sum())
            stats["router_assignments"] = int(aux[..., 4].sum())
        return stats

    def _step_unified(self, params) -> int:
        """One ragged round: host-only admission, ONE dispatch serving
        the mixed prefill+decode batch, unpack. The single device→host
        transfer is the step's emitted tokens.

        Every stretch of host work sits in a ``phase`` (``cbe.admit`` ..
        ``cbe.audit``), so a profiler trace says what the host did in each
        gap the device idles through."""
        with phase("cbe.admit"):
            admitted = self._admit()
        if not self._live:
            if self._check_invariants:
                with phase("cbe.audit"):
                    self.mgr.check_conservation()
            return 0
        fresh = (self._unified_step is None
                 or self._unified_flags != _prefill_flags())
        if fresh:
            # the engine's ONE compile-cache miss (plus at most one
            # device remat): every later step reuses this program. A
            # set_flags flip of host state the program bakes in (see
            # _prefill_flags) is the ONE sanctioned extra miss — counted
            # here instead of silently serving the stale program.
            self._unified_flags = _prefill_flags()
            recompiles.record_miss(
                "cbe.unified_step",
                (self.num_slots, self.chunk, self._step_tokens,
                 self._table_width, self._fused_tail, self.num_chips,
                 self._epilogue_on)
                + self._unified_flags)
            self._unified_step = self._build_unified_step()
        # armed-only continuous-profiling taps: the plan -> dispatch ->
        # unpack phases are the fusion pass's decode_tail signature
        # (jit/fusion.py); disarmed cost is one list index per step
        armed_chain = _chain_armed[0]
        tc0 = time.perf_counter_ns() if armed_chain else 0
        with phase("cbe.plan"):
            if self._fused_tail:
                plan_tt, plan_tr, emit, emit_counts, fed = \
                    self._plan_step_packed()
                plan = (plan_tt, plan_tr)
                record = self._dispatch_record(
                    plan_tt[2], plan_tt[3], plan_tr[0], emit_counts, fed)
            else:
                plan, emit, emit_counts, fed = self._plan_step()
                record = self._dispatch_record(
                    plan[2], plan[3], plan[4], emit_counts, fed)
        if armed_chain:
            tc1 = time.perf_counter_ns()
            _note_chain(op_name="cbe.plan_step", dur_ns=tc1 - tc0)
            tc0 = tc1
        # tokens that actually run through prefill THIS step (cancelled
        # mid-prefill requests never inflate the skip-ratio math)
        self._prefill_tokens += sum(fed)
        if fresh:
            c0 = time.perf_counter()   # dispatch-only window
        t0_ns = time.perf_counter_ns() if spans_armed() else 0
        # how often admission happens and how often it costs a transfer:
        # the requests this step admitted, the prompt tokens of theirs the
        # prefix cache already held (so a trace shows whether a dispatch's
        # new rows were hits), and 1 where one of them changed a row's
        # sampler parameters or restarts a grammar state (the mirrors then
        # ride this upload)
        row_state = int(self._samp_dirty or self._greset_dirty)
        with phase("cbe.upload", admitted=admitted,
                   row_state_uploads=row_state,
                   cached_tokens=self._admitted_cached):
            plan_dev = [jnp.asarray(a) for a in plan]
            greset = self._upload_row_state()
            gtable = self._arena.device_table()
            bt = jnp.asarray(self._bt)
        with phase("cbe.dispatch", **record):       # enqueue only
            # the cache's arrays, pages then the rows' state: one donated
            # pytree in, the same out
            (toks, self._tok_dev, self._gstate_dev, self.mgr.arrays,
             *aux) = self._unified_step(
                params, *plan_dev,
                self._tok_dev, self._gstate_dev, greset, self._samp_dev,
                gtable, self.mgr.arrays, bt)
        with phase("cbe.fence"):
            if fresh:
                jax.block_until_ready(toks)
                recompiles.observe_compile("cbe.unified_step",
                                           time.perf_counter() - c0)
            toks = np.asarray(toks)                    # the one fence
            # a model with experts: its routing record comes with the
            # tokens, and its sums ride on the unpack span
            routed = self._expert_stats(np.asarray(aux[0])) if aux else {}
        if armed_chain:
            tc1 = time.perf_counter_ns()
            if self._fused_tail:
                _note_chain(op_name="cbe.fused_unified_step",
                            dur_ns=tc1 - tc0)
            else:
                _note_chain(op_name="cbe.unified_step", dur_ns=tc1 - tc0)
            if self._epilogue_active():
                # the sampling epilogue runs inside the dispatch above;
                # this zero-duration tap makes it visible to the fusion
                # pass's chain mining (REGIONS["sampling_epilogue"])
                _note_chain(op_name="cbe.sample_epilogue", dur_ns=0)
            tc0 = tc1
        with phase("cbe.unpack", **routed):
            if t0_ns:
                # per-request phase bookkeeping over the dispatch window:
                # the trace keeps its prefill/decode lanes even though
                # both ride one program. Runs EVERY armed step, so it only
                # updates the per-slot coalesced windows (a few list ops)
                # — spans materialise at phase change / retire, keeping
                # the armed loop inside bench_obs_overhead's budget
                t1_ns = time.perf_counter_ns()
                batch: list = []
                win = self._win
                for s in range(self.num_slots):
                    if self._slot_rid[s] is None:
                        continue
                    c = emit_counts[s]
                    f = fed[s]
                    if (c == 0) != (f == 0):
                        # steady-state single-phase round: extend the
                        # window inline (no function call — this branch
                        # is the armed hot path every decode step takes)
                        w = win[s]
                        kind = "decode" if c else "prefill"
                        if w is not None and w[0] == kind:
                            w[2] = t1_ns
                            w[3] += c or f
                            continue
                    if f > 0:
                        self._note_win(s, "prefill", t0_ns, t1_ns, f, batch)
                    if c:
                        self._note_win(s, "decode", t0_ns, t1_ns, c, batch)
                if batch:
                    emit_spans(batch)
            for s in range(self.num_slots):
                if self._slot_rid[s] is None:
                    continue
                if self._fused_tail and emit_counts[s] == self.chunk:
                    # fused-tail fast unpack: the slot emitted every
                    # round, so its column IS the emission (no K-wide
                    # mask filter)
                    self._deliver_tokens(s, toks[:, s])
                else:
                    self._deliver_tokens(
                        s, (toks[k, s] for k in range(self.chunk)
                            if emit[k, s]))
        if armed_chain:
            _note_chain(op_name="cbe.decode_tail",
                        dur_ns=time.perf_counter_ns() - tc0)
        if self.cache is not None:
            with phase("cbe.audit"):
                if self._check_invariants:
                    # the ownership-model anchor: every page is free, live
                    # (refcounted) or cached — checked after EVERY ragged
                    # step, COW suffix rows included
                    self.mgr.check_conservation()
                self.cache.update_gauges()
        return len(self._live)

    # -- speculative decoding (draft + verify in ONE ragged dispatch) --------

    def _build_spec_step(self):
        """ONE compiled program for every speculative round the engine
        will ever run: a single ragged model step whose logits are taken
        at EVERY packed candidate index (``cand_idx`` — the generalized
        ``last_idx`` of ``models.llama.ragged_step``) and argmax'd
        in-program. A speculating row's span ``[carry, d1..dk]`` is just
        a short prefill at consecutive positions under the kernel's one
        ``key_pos <= position`` mask rule, so the per-candidate greedy
        tokens that come back ARE the verifier: ``g[j]`` is the model's
        next token after the row's history + ``span[0..j]``, valid
        exactly while the drafted prefix matches — the host accepts the
        longest matching prefix plus the bonus token. Shapes depend only
        on (spec_tokens, slots*(k+1), table width) fixed at construction
        — the request mix, draft lengths and acceptance history never
        recompile anything."""
        L = self._L
        mcfg = self.model_config
        mesh, mp_axis = self._mesh, self._mp_axis
        n_rows, k1 = self.num_slots, self.spec_k + 1
        # lazy epilogue, spec flavour: argmax + prefix-match verify
        # until the first sampler/grammar submit (see _build_unified_step)
        tail = (_sampling.spec_sample_rows if self._epilogue_on
                else _sampling.spec_greedy_rows)
        if self._fused_tail:
            # fused decode tail, spec flavour: the same single ragged
            # dispatch plus the verify epilogue IN-PROGRAM — greedy rows
            # the vectorized accepted-prefix count, sampled rows the
            # rejection-sampling verifier (jit/fusion.py)
            from ..jit import fusion as _fusion

            def model_step(params, ids, token_row, positions, kv_lens,
                           cand_idx, pools, bt):
                return _split_step(L.ragged_step(
                    params, ids, token_row, positions, kv_lens, cand_idx,
                    *pools, bt, mcfg, mesh=mesh, mp_axis=mp_axis),
                    len(pools))[:2]

            return _fusion.build_fused_spec_step(
                model_step, tail, _constrain.reset_states, self.spec_k,
                n_rows)

        def run(params, ids, token_row, positions, kv_lens, cand_idx,
                drafts, draft_len, sampled, gstate, greset, samp, gtable,
                pools, bt):
            gstate = _constrain.reset_states(gstate, greset)
            logits, pools, _ = _split_step(L.ragged_step(
                params, ids, token_row, positions, kv_lens, cand_idx,
                *pools, bt, mcfg, mesh=mesh, mp_axis=mp_axis), len(pools))
            # the speculative sampling epilogue (spec_sample_rows):
            # greedy rows keep the per-candidate argmax + prefix-match
            # verify (byte-identical to the pre-sampling program),
            # sampled rows run lossless rejection sampling — the fence
            # stays (slots, k+1) int32 + (slots,) accepted instead of
            # shipping full (C, V) logits to the host
            lg = logits.reshape(n_rows, k1, -1)
            pos_base = jnp.take(positions,
                                cand_idx.reshape(n_rows, k1)[:, 0])
            toks, accepted, ngst = tail(
                lg, drafts, draft_len, pos_base, samp, gstate, gtable)
            # only rows that really committed a token advance their
            # grammar state (a mid-prefill constrained row's candidate
            # slot holds garbage)
            gstate = jnp.where(sampled, ngst, gstate)
            return toks, accepted, gstate, pools

        return jax.jit(run, donate_argnums=(13,))

    def _plan_spec(self):
        """Host layout of one speculative round. Every decode row claims
        a span of ``[carry] + up to spec_k drafted tokens`` — its page
        table grows to cover the speculative tail (``mgr.grow_to``);
        pool pressure or the block-table span shrink the draft, never
        fail the round. Prefill rows share the remaining packed budget
        exactly like ``_plan_step``'s single micro-round. Returns the
        device metadata arrays, the per-slot verify plan and the
        per-slot prefill-token counts."""
        T, n_rows = self._spec_tokens, self.num_slots
        k1 = self.spec_k + 1
        cap_tokens = self._table_width * self.page_size
        ids = np.zeros((T,), np.int32)
        token_row = np.full((T,), -1, np.int32)
        positions = np.zeros((T,), np.int32)
        # per-row padded drafts for the in-program verify epilogue
        # (both tails consume them since the rejection-sampling
        # verifier moved the accept/reject in-program)
        drafts = np.zeros((n_rows, max(self.spec_k, 1)), np.int32)
        draft_len = np.zeros((n_rows,), np.int32)
        # rows committing a token this round (spec spans + completed
        # prefills): gates the in-program grammar-state advance
        sampled = np.zeros((n_rows,), bool)
        kv_lens = np.zeros((n_rows,), np.int32)
        cand_idx = np.zeros((n_rows * k1,), np.int32)
        info: Dict[int, tuple] = {}
        fed = [0] * n_rows
        live = [s for s in range(n_rows) if self._slot_rid[s] is not None]
        spans: Dict[int, tuple] = {}
        armed = spans_armed()
        draft_spans: list = []
        for s in live:
            if self._pend[s] is not None:
                continue                      # prefilling: planned below
            rid = self._slot_rid[s]
            req = self._live[rid]
            d0_ns = time.perf_counter_ns() if armed else 0
            # committed history (prompt + delivered tokens; the last
            # delivered token IS the carry whose K/V this round writes)
            history = [int(t) for t in req.prompt] + req.tokens
            if req.grammar is not None:
                # constrained rows NEVER draft: candidates past the
                # carry would be verified against un-advanced grammar
                # states (the mask covers candidate 0 only), so an
                # accepted draft could smuggle an illegal token. One
                # candidate per round keeps every emitted token legal.
                draft = []
            else:
                draft = [int(t) for t in
                         self.drafter.draft(history, self.spec_k)]
            pos0 = int(self._pos[s])
            # clamp the draft to (a) the remaining token budget: a
            # round commits at most accepted+1 <= len(draft)+1 tokens
            # and _deliver_tokens trims at the budget, so positions
            # past rem-1 could never commit — verifying them would be
            # pure waste and the page they'd grow would be freed right
            # back; (b) the row's block-table span (the model clips
            # positions past it into the last slot, which would corrupt
            # real pages)
            rem = self._budget(req) - len(req.tokens)
            draft = draft[:max(0, min(self.spec_k, rem - 1,
                                      cap_tokens - 1 - pos0))]
            # ensure the page table covers the span. With the budget
            # clamp above the span sits inside the admission
            # reservation and this is a no-op; it is the engine's
            # safety net (and the hook a lazy-allocation admission mode
            # would grow through — mgr.grow_to/truncate_pages are
            # exercised as the speculative substrate by the kvcache
            # interleaving property test). Under pool pressure the
            # draft shrinks; the carry's own slot always fits.
            while True:
                try:
                    self.mgr.grow_to(rid, pos0 + len(draft) + 1)
                    break
                except MemoryError:
                    draft.pop()
            tbl = self.mgr._tables[rid]
            self._bt[s] = 0
            self._bt[s, :len(tbl)] = tbl
            if d0_ns:
                # host-side drafting (n-gram lookup / draft model +
                # speculative page growth) is its own timeline segment,
                # split from the verify dispatch (engine.spec_round)
                draft_spans.append(make_span(
                    "engine.spec_draft", d0_ns, time.perf_counter_ns(),
                    "Operator", req.trace_id,
                    args={"request_id": rid, "slot": s,
                          "drafted": len(draft)}))
            spans[s] = (pos0, [history[-1]] + draft, draft)
            if draft:
                drafts[s, :len(draft)] = draft
            draft_len[s] = len(draft)
        emit_spans(draft_spans)
        budget = T - sum(1 + len(d) for _, _, d in spans.values())
        cursor = 0
        for s in live:
            if s in spans:                    # decode: speculative span
                pos0, span, draft = spans[s]
                n = len(span)
                ids[cursor:cursor + n] = span
                token_row[cursor:cursor + n] = s
                positions[cursor:cursor + n] = pos0 + np.arange(n)
                kv_lens[s] = pos0 + n
                cand_idx[s * k1:s * k1 + n] = cursor + np.arange(n)
                info[s] = ("spec", pos0, draft)
                sampled[s] = True
                cursor += n
            else:                             # prefilling
                rem = len(self._pend[s])
                n = min(rem, budget)
                if n == 0:
                    continue                  # starved this round
                pos0 = int(self._pos[s])
                ids[cursor:cursor + n] = self._pend[s][:n]
                token_row[cursor:cursor + n] = s
                positions[cursor:cursor + n] = pos0 + np.arange(n)
                kv_lens[s] = pos0 + n
                budget -= n
                fed[s] = n
                self._pos[s] = pos0 + n
                if n == rem:
                    # prompt complete: this round's last logits are the
                    # row's first sample
                    cand_idx[s * k1] = cursor + n - 1
                    info[s] = ("first_sample",)
                    sampled[s] = True
                    self._pend[s] = None
                else:
                    self._pend[s] = self._pend[s][n:]
                cursor += n
        return ((ids, token_row, positions, kv_lens, cand_idx), info, fed,
                drafts, draft_len, sampled)

    def _verify_spec(self, toks, info, accepted):
        """Host commit over the dispatch's per-row verified tokens
        (``toks (slots, k+1)``, ``accepted (slots,)`` — both computed
        in-program by the verify/sampling epilogue, fused and unfused
        alike): deliver the accepted drafted prefix plus the epilogue's
        token at the first rejected lane (greedy: the model's own
        argmax; sampled: the rejection-sampling residual draw / the
        bonus draw), roll the paged KV back on rejection, deliver
        through the shared ``_deliver_tokens`` contract (callbacks,
        budget/EOS retire, reentrant cancel)."""
        for s in sorted(info):
            rid = self._slot_rid[s]
            if rid is None:
                continue                    # retired by a reentrant cancel
            entry = info[s]
            if entry[0] == "first_sample":
                self._deliver_tokens(s, [int(toks[s, 0])])
                continue
            _, pos0, draft = entry
            a = min(int(accepted[s]), len(draft))
            committed = pos0 + a + 1        # carry + accepted drafts
            self.spec.note_verify(len(draft), a)
            if a < len(draft):
                # rejection rollback: stale K/V *within* kept pages is
                # overwritten before anything attends to it (scatter-
                # first), but a page that exists only for rejected
                # positions is stranded — deref/free it now, never
                # dropping below the admission reservation
                keep = max(self.mgr.pages_for(committed),
                           int(self._reserved[s]))
                freed = self.mgr.truncate_pages(rid, keep)
                tbl = self.mgr._tables[rid]
                self._bt[s] = 0
                self._bt[s, :len(tbl)] = tbl
                self.spec.note_rollback(len(freed))
                emit_event("spec_rollback", request_id=rid,
                           trace_id=self._live[rid].trace_id,
                           drafted=len(draft), accepted=a,
                           freed_pages=len(freed))
            self._pos[s] = committed
            self.mgr._lens[rid] = committed
            self._deliver_tokens(
                s, [int(t) for t in draft[:a]] + [int(toks[s, a])])

    def _step_spec(self, params) -> int:
        """One speculative round: host-only admission, drafting + page
        growth, ONE dispatch whose candidate argmaxes verify every
        row's draft, host accept/reject + paged rollback. The single
        device→host transfer is the ``(slots*(spec_k+1),)`` candidate
        token vector — smaller than the unified step's emit matrix."""
        self._admit()
        if not self._live:
            if self._check_invariants:
                self.mgr.check_conservation()
            return 0
        fresh = (self._spec_step is None
                 or self._spec_flags != _prefill_flags())
        if fresh:
            # the speculative engine's ONE compile-cache miss; a
            # set_flags flip of baked-in host state is the one
            # sanctioned extra (same contract as the unified step)
            self._spec_flags = _prefill_flags()
            recompiles.record_miss(
                "cbe.spec_step",
                (self.num_slots, self._spec_tokens, self.spec_k,
                 self._table_width, self._fused_tail, self.num_chips,
                 self._epilogue_on)
                + self._spec_flags)
            self._spec_step = self._build_spec_step()
        armed_chain = _chain_armed[0]
        tc0 = time.perf_counter_ns() if armed_chain else 0
        plan, info, fed, drafts, draft_len, sampled = self._plan_spec()
        if armed_chain:
            tc1 = time.perf_counter_ns()
            _note_chain(op_name="cbe.plan_step", dur_ns=tc1 - tc0)
            tc0 = tc1
        self._prefill_tokens += sum(fed)
        if fresh:
            c0 = time.perf_counter()
        t0_ns = time.perf_counter_ns() if spans_armed() else 0
        # fused and unfused spec programs share one signature since the
        # verify/sampling epilogue moved in-program for both
        toks, accepted, self._gstate_dev, self.mgr.pools = self._spec_step(
            params, *(jnp.asarray(a) for a in plan),
            jnp.asarray(drafts), jnp.asarray(draft_len),
            jnp.asarray(sampled), self._gstate_dev,
            self._upload_row_state(), self._samp_dev,
            self._arena.device_table(), self.mgr.pools,
            jnp.asarray(self._bt))
        if fresh:
            jax.block_until_ready(toks)
            recompiles.observe_compile("cbe.spec_step",
                                       time.perf_counter() - c0)
        toks = np.asarray(toks)                    # the one fence
        accepted = np.asarray(accepted)
        if armed_chain:
            tc1 = time.perf_counter_ns()
            if self._fused_tail:
                _note_chain(op_name="cbe.fused_spec_step",
                            dur_ns=tc1 - tc0)
            else:
                _note_chain(op_name="cbe.spec_step", dur_ns=tc1 - tc0)
            if self._epilogue_active():
                _note_chain(op_name="cbe.sample_epilogue", dur_ns=0)
            tc0 = tc1
        if t0_ns:
            t1_ns = time.perf_counter_ns()
            batch = []
            for s in range(self.num_slots):
                rid = self._slot_rid[s]
                if rid is None:
                    continue
                req = self._live[rid]
                if fed[s] > 0:
                    batch.append(make_span(
                        "engine.prefill", t0_ns, t1_ns, "Operator",
                        req.trace_id,
                        args={"request_id": rid, "slot": s,
                              "prefill_tokens": int(fed[s])}))
                if info.get(s, ("",))[0] == "spec":
                    batch.append(make_span(
                        "engine.spec_round", t0_ns, t1_ns, "Operator",
                        req.trace_id,
                        args={"request_id": rid, "slot": s,
                              "drafted": len(info[s][2])}))
            emit_spans(batch)
        self._verify_spec(toks, info, accepted)
        if armed_chain:
            _note_chain(op_name="cbe.decode_tail",
                        dur_ns=time.perf_counter_ns() - tc0)
        if self._check_invariants:
            # the ownership-model anchor, now also covering draft
            # growth and rejection rollback: audited after EVERY
            # speculative step (spec mode runs it even cache-off — the
            # base manager grew an exclusive-ownership audit for this)
            self.mgr.check_conservation()
        if self.cache is not None:
            self.cache.update_gauges()
        return len(self._live)

    def collect(self) -> Dict[int, list]:
        out = self._finished
        self._finished = {}
        return out

    def finished_checksum(self, rid: int) -> Optional[int]:
        """crc32 of the tokens ``_retire`` produced for ``rid`` (None if
        the request never finished, e.g. cancelled). Survives
        ``collect()`` so serving layers can stamp terminal journal
        frames after draining the finished map."""
        return self._finished_crc.get(rid)

    def serve(self, params, prompts) -> list:
        """Stream a list of prompts through the fixed slots; returns the
        generated token lists in submission order."""
        rids = [self.submit(p) for p in prompts]
        results: Dict[int, list] = {}
        while len(results) < len(rids):
            self.step(params)
            results.update(self.collect())
            if not self._live and not self._queue and \
                    len(results) < len(rids):
                raise RuntimeError("serve stalled with pending requests")
        return [results[r] for r in rids]

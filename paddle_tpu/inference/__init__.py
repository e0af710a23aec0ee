"""``paddle_tpu.inference`` — deployment API.

Rebuild of the reference's inference stack (paddle/fluid/inference/api/
analysis_predictor.cc, python/paddle/inference/ — SURVEY.md §2.5 inference
row, §3.5 call stack): ``Config`` + ``create_predictor`` + named IO handles.

TPU-first: the AnalysisPredictor's IR-fusion passes and the TensorRT
subgraph engine are XLA's job — the loaded artifact is already a compiled
StableHLO program (jit.save), so ``create_predictor`` is a thin wrapper:
load → bind IO handles → ``run()`` executes the XLA executable. The serving
decode loop with KV cache lives in paddle_tpu.inference.decoding.
"""

from .config import Config  # noqa: F401
from .predictor import Predictor, create_predictor  # noqa: F401
from . import decoding  # noqa: F401
from .decoding import (  # noqa: F401
    ContinuousBatchingEngine, GenerationConfig,
)
from .speculative import (  # noqa: F401
    Drafter, DraftModel, NgramDrafter, SpeculationTelemetry,
)
from . import sampling  # noqa: F401
from . import constrain  # noqa: F401
from .sampling import SamplerConfig  # noqa: F401
from .constrain import (  # noqa: F401
    GrammarArena, TokenDFA, compile_regex, json_grammar, json_regex,
)

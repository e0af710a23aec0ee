"""Engine host loop: the garbage collector's pauses: the summed duration of
the program's ``paddle_serving.gc`` spans (one a collection, of every host
thread: a collection holds them all) in the traced window over the window's
dispatches (``perfbench/host_spans.py``). 0.0 where the program writes the
span and the window held no collection; left out on a program that does not
write it."""

from perfbench import host_spans


def read(obs):
    t = host_spans.for_obs(obs)
    if t is None:
        return None
    return 1e3 * t["by_name"][host_spans.GC]["s"] / t["dispatches"]

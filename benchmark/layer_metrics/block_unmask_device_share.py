"""Device time of what decides a pass's tokens (the final norm's head over
every position of every lane's block, the softmax's pieces, the
confidence and the selection: the program's ``block_unmask`` scope) over
the burst executable's, over the capture. The trace's reduction keeps an
op's instruction name and result shape and drops the scope it was traced
under (PERF.md, section 7), so the ops are found by what only they
produce inside ``jit_fused_burst``: a result as wide as the vocabulary.
The reductions that end in one number a row are left out: a lower bound.
None where the program names no block pass, or the burst has no such op
among the ops the reduction names."""
from benchmark import capture, trace

BURST = "jit_fused_burst"


def read(run):
    if "block_forwards" not in capture.counters(run):
        return None
    seconds, runs = trace.module_seconds(run["trace"] or {}, BURST)
    wide = f"_{run['config']['vocab_size']}"
    unmask = sum(s for name, s in (run["trace"] or {}).get("device_ops", [])
                 if name.startswith(BURST) and name.endswith(wide))
    if not runs or seconds <= 0 or unmask <= 0:
        return None
    return 100.0 * unmask / seconds

"""Device time of the convolution layers' input projection, ``[B | C | u] =
x W_in``, inside the decode burst over the burst executable's, over the
capture: three quarters of a gated short convolution's weights, ten layers
of them a step. It is NOT the whole operator: the program traces that under
the scope ``short_conv`` (from ``x W_in`` to ``W_out``), but the trace's
reduction keeps an op's instruction name and result shape, drops the scope
it was traced under and names the ten ops of most time (PERF.md, section
7), so the product is found by the one result only it has inside
``jit_fused_burst``: [lanes, 3 x hidden]. ``W_out``'s product has the
residual stream's shape, as every layer's, and the tails' update is far
below the ten named ops: neither is read, and this metric does not guard
them. None where the program counts no convolution tails, or where the
product is not among the ops the reduction names (XLA fused or renamed it,
or ten other ops took more time)."""
from benchmark import capture, trace

BURST = "jit_fused_burst"


def read(run):
    if capture.counters(run).get("conv_tails_written", 0) <= 0:
        return None
    cfg = run["config"]
    seconds, runs = trace.module_seconds(run["trace"] or {}, BURST)
    mine = f"_{cfg['server']['slots']}_{3 * cfg['hidden_size']}"
    conv = sum(s for name, s in (run["trace"] or {}).get("device_ops", [])
               if name.startswith(BURST) and name.endswith(mine))
    if not runs or seconds <= 0 or conv <= 0:
        return None
    return 100.0 * conv / seconds

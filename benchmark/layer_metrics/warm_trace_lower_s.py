"""The seconds of warm-up JAX spent tracing and lowering:
``stages.warm.trace_s + lower_s`` of the compile log
(``tracing.CompileLog``): what a warm compile cache does not save, and
where a family that unrolls its layers in Python grows. None without the
log or the stage."""
from benchmark import capture


def read(run):
    log = (capture.report(run) or {}).get("compiles") or {}
    warm = log.get("stages", {}).get("warm")
    return warm["trace_s"] + warm["lower_s"] if warm else None

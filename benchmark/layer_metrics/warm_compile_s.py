"""The seconds of warm-up inside XLA's backend: ``stages.warm.backend_s`` of
the compile log (``tracing.CompileLog``; the persistent cache's retrieval
is inside the event, so a warm cache reads its reads). With
``warm_trace_lower_s`` at most ``warm_s``. None without the log or the
stage."""
from benchmark import capture


def read(run):
    log = (capture.report(run) or {}).get("compiles") or {}
    warm = log.get("stages", {}).get("warm")
    return warm["backend_s"] if warm else None

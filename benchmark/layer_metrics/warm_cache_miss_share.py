"""What of the set-up's compiles the persistent compile cache did not hold:
``cache_misses / (cache_hits + cache_misses)`` over stages ``load`` and
``warm`` of the compile log (``tracing.CompileLog``), in %. 100 on a cold
machine, 0 on one that ran the cell before. None without the log, and where
the cache said nothing (it is off)."""
from benchmark import capture


def read(run):
    log = (capture.report(run) or {}).get("compiles") or {}
    stages = [log.get("stages", {}).get(s, {}) for s in ("load", "warm")]
    hits = sum(s.get("cache_hits", 0) for s in stages)
    misses = sum(s.get("cache_misses", 0) for s in stages)
    if not hits + misses:
        return None
    return 100.0 * misses / (hits + misses)

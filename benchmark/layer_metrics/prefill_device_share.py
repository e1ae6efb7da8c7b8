"""Device seconds of the prefill executables (whole-prompt, batched,
chunked, prefix-suffix) over the device's busy seconds of the trace."""
from benchmark import trace

PREFILLS = ("jit_prefill_one", "jit_prefill_many", "jit_chunk_prefill",
            "jit_prefix_prefill")


def read(run):
    t = run["trace"]
    if not t or not t.get("busy_s"):
        return None
    seconds, _runs = trace.module_seconds(t, *PREFILLS)
    return 100.0 * seconds / t["busy_s"]

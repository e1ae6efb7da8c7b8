"""Chunks of the gated delta rule that the prefills walked over the chunks
of the buckets they were padded to: the program's
``gdn_prefill_chunks_walked / gdn_prefill_chunks_bucket`` over the capture,
each counted per (sequence, linear layer) beside the call, from ``lens``
and the bucket (``models/qwen3_next.py``). The prefill kernel
(``ops/gated_delta.py:gated_delta_prefill``) neither fetches nor computes a
chunk at or past ``ceil(lens / 64)``, so this is the share of the scan's
turns that were run: 68.1% over a cycle of the longbatch mix (188 of 276:
2048 and 3328 tokens in the 4096 bucket), 100% where every prompt fills its
bucket. None where the program has no such counters."""
from benchmark import capture


def read(run):
    c = capture.counters(run)
    bucket = c.get("gdn_prefill_chunks_bucket", 0)
    if bucket <= 0 or "gdn_prefill_chunks_walked" not in c:
        return None
    return 100.0 * c["gdn_prefill_chunks_walked"] / bucket

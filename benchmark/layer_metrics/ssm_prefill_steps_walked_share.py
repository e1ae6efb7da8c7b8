"""Steps of the selective scan that the prefills walked over the steps of
the buckets they were padded to: the program's ``ssm_prefill_steps_walked /
ssm_prefill_steps_bucket`` over the capture, each counted per (sequence,
Mamba layer) beside the call, from ``lens`` and the bucket
(``models/jamba.py``). The prefill kernel
(``ops/selective_scan.py:selective_scan_prefill``) neither fetches nor
computes a step at or past a sequence's length (fetched in chunks of 64,
computed in blocks of 16), so this is the share of the bucket's turns that
were run: 77.7% over a cycle of the thinking mix (1,690 of 2,176: 90 in
128, 250 and 450 in 512, 900 in 1,024), 100% where every prompt fills its
bucket. None where the program has no such counters."""
from benchmark import capture


def read(run):
    c = capture.counters(run)
    bucket = c.get("ssm_prefill_steps_bucket", 0)
    if bucket <= 0 or "ssm_prefill_steps_walked" not in c:
        return None
    return 100.0 * c["ssm_prefill_steps_walked"] / bucket

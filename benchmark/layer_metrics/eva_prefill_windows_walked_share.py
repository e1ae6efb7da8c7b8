"""Windows the prefills walked over the windows of the buckets they were
padded to: the program's ``eva_prefill_windows_walked /
eva_prefill_windows_bucket`` over the capture, each counted per prompt
beside the call from its length and the bucket (``models/evabyte.py``). A
prompt past a window is prefilled by one executable that walks its own
``ceil(len / 2048)`` windows of the 16,384 bucket's 8; one inside a window
takes a bucket of one window. 48% over a cycle of the bytebatch mix (1 of
1, and 2, 3 and 6 of 8: 12 of 25), 100% on a program that pads every
prompt to its bucket. None where the program has no such counters."""
from benchmark import capture


def read(run):
    c = capture.counters(run)
    bucket = c.get("eva_prefill_windows_bucket", 0)
    if bucket <= 0 or "eva_prefill_windows_walked" not in c:
        return None
    return 100.0 * c["eva_prefill_windows_walked"] / bucket

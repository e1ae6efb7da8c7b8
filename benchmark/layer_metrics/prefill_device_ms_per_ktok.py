"""Device time of ``prefill_one`` + ``prefill_many`` over the real
(unpadded) prompt kilotokens: sequences admitted during the trace times the
mix's mean prompt length, which the stratified traffic fixes."""
from benchmark import trace, traffic


def read(run):
    got = trace.prefill_work(run)
    if got is None:
        return None
    seconds, _padded, sequences = got
    return 1e3 * seconds / (sequences * traffic.mean_prompt(run["traffic"]) / 1e3)

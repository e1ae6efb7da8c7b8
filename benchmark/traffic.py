"""The one traffic generator: a mix is a data file, this reads it.

Steady by construction. A mix lists length classes ``[prompt_tokens,
max_new_tokens, count]`` that make one cycle. The seed permutes the order
inside each cycle, places each open-loop arrival inside its own slot of
``1/rate`` seconds and draws the token ids; it never changes how many
requests of which class a run sends, nor how many arrive in a window.
"""

from __future__ import annotations

import json
import random


def _rng(seed: int, what: str, index: int = 0) -> random.Random:
    # a string seed is hashed with SHA-512: the same on every run and
    # machine, and any whole number of any size is a fine --seed
    return random.Random(f"{seed}:{what}:{index}")


def cycle(mix: dict) -> list:
    """One cycle, in file order: ``[(class index, prompt, new), ...]``."""
    out = []
    for k, (prompt, new, count) in enumerate(mix["classes"]):
        out.extend([(k, int(prompt), int(new))] * int(count))
    return out


def request_class(mix: dict, seed: int, i: int) -> tuple:
    """Class of request ``i``: cycle ``i // n`` in an order of its own."""
    base = cycle(mix)
    order = list(range(len(base)))
    _rng(seed, "cycle", i // len(base)).shuffle(order)
    return base[order[i % len(base)]]


def prompt_tokens(seed: int, i: int, n: int, vocab: int) -> list:
    return _rng(seed, "prompt", i).choices(range(vocab), k=n)


def request_body(mix: dict, seed: int, i: int, vocab: int) -> tuple:
    """``(class index, prompt_len, max_new, body bytes)`` of request ``i``.
    No ``eos_id``: every request yields exactly ``max_new`` tokens."""
    k, n_prompt, n_new = request_class(mix, seed, i)
    body = {"jsonData": {
        "prompt_tokens": [prompt_tokens(seed, i, n_prompt, vocab)],
        "max_new_tokens": n_new,
        "temperature": float(mix.get("temperature", 0.0)),
    }}
    return k, n_prompt, n_new, json.dumps(body).encode()


def arrival(mix: dict, seed: int, i: int) -> float:
    """Seconds after load starts at which open-loop request ``i`` is due:
    uniform inside slot ``i`` of ``1/rate`` seconds, so any window holds
    the same number of arrivals to within one."""
    return (i + _rng(seed, "arrival", i).random()) / float(mix["rate_rps"])


def n_clients(mix: dict, slots: int) -> int:
    """Closed loop: ``per_slot`` clients for each decode lane of the
    configuration, plus ``extra``."""
    c = mix["clients"]
    return max(1, int(round(c.get("per_slot", 0) * slots)) + int(c.get("extra", 0)))


def prompt_lens(mix: dict) -> list:
    return sorted({int(c[0]) for c in mix["classes"]})


def max_new(mix: dict) -> int:
    return max(int(c[1]) for c in mix["classes"])


def mean_prompt(mix: dict) -> float:
    base = cycle(mix)
    return sum(p for _k, p, _n in base) / len(base)

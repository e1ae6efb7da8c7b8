"""Random weights for a cell, drawn on the device in one jitted call.

``DecoderLM.init_params`` draws every matrix in float32 with an eagerly
compiled program of its own: at 7B widths the float32 tree does not fit the
chip beside anything else, and each matrix costs a compilation. A real
checkpoint pays neither; a synthetic cell would pay both on every run. So
the benchmark's model directory names the family ``benchmark_llm`` below,
registered through the program's own ``models.register``: the same
``DecoderLM`` in every method but this one, which runs the program's own
draw under one ``jit`` and casts each leaf to the served dtype inside it.
The values are ``init_params(seed)``'s, rounded once to bfloat16 as
``GenerateServer.load`` would round them.
"""

from __future__ import annotations

from seldon_core_tpu.models.llm import DecoderLM

FAMILY = "benchmark_llm"


class SeededDecoderLM(DecoderLM):
    def init_params(self, seed: int = 0):
        import jax
        import jax.numpy as jnp

        dt = jnp.dtype(self.cfg.dtype)
        draw = super().init_params

        def served(s):
            return jax.tree_util.tree_map(lambda a: a.astype(dt), draw(s))

        return jax.jit(served)(jnp.uint32(seed))


def register() -> None:
    from seldon_core_tpu import models

    models.register(FAMILY, f"{__name__}.SeededDecoderLM")

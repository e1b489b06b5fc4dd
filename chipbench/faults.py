"""The faults a training cell can have, planted under the timed path.

`chipbench/tests` plants each and sees `correct` come out false, and
`tools/readings.py` reads on the chip what each does to the numbers
compared. The benchmark's own runs never import this file.

    unchanged   a step that returns its state unchanged
    half_batch  half of the batch left out, the mean taken over the rest
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

FAULTS = ("unchanged", "half_batch")


def _copy(tree):
    return jax.tree.map(jnp.copy, tree)


def _image_step(step, fault):
    def unchanged(state, rng, inputs, labels):
        _, loss, metrics = step(_copy(state), rng, inputs, labels)
        return state, loss, metrics

    def half_batch(state, rng, inputs, labels):
        half = lambda t: jax.tree.map(lambda x: x[:x.shape[0] // 2], t)
        return step(state, rng, half(inputs), half(labels))

    return {"unchanged": unchanged, "half_batch": half_batch}[fault]


def _lm_step(step, fault):
    def unchanged(state, toks):
        return state, step(_copy(state), toks)[1]

    def half_batch(state, toks):
        return step(state, toks[:toks.shape[0] // 2])

    return {"unchanged": unchanged, "half_batch": half_batch}[fault]


def plant(driver, fault: str) -> None:
    """Break the step of a driver that has been built."""
    if hasattr(driver, "trainer"):
        driver.trainer._train_step = _image_step(driver.trainer._train_step,
                                                 fault)
    else:
        driver.step = _lm_step(driver.step, fault)

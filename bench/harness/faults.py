"""Faults planted under the timed path, to show that ``correct`` catches
them: a served token altered where it is produced, a train step that
returns its state unchanged, and a train step that leaves out half of the
batch and takes the mean over the rest. Each ``plant`` returns the
function that takes the fault out again. The benchmark's own runs never
plant one; ``control.py --fault`` and the self-checks do."""
from __future__ import annotations

from typing import Callable


def token_altered() -> Callable[[], None]:
    """Every decode step's next token is moved one id up the vocabulary
    (the logits are left as they are)."""
    from repro.core.runtime import ModelRuntime
    orig = ModelRuntime.paged_decode_fn

    def broken(self):
        fn = orig(self)
        vocab = self.cfg.vocab_size

        def step(*args):
            nt, logits, state = fn(*args)
            return (nt + 1) % vocab, logits, state
        return step
    ModelRuntime.paged_decode_fn = broken
    return lambda: setattr(ModelRuntime, "paged_decode_fn", orig)


def _wrap_train_step(wrap) -> Callable[[], None]:
    from . import train as train_lib
    orig = train_lib.build

    def build(*a, **k):
        step, *rest = orig(*a, **k)
        return (wrap(step), *rest)
    train_lib.build = build
    return lambda: setattr(train_lib, "build", orig)


def state_unchanged() -> Callable[[], None]:
    """The step computes as usual but hands back the state it was given."""
    import jax
    import jax.numpy as jnp

    def wrap(step):
        def broken(params, trainable, state, batch):
            keep_t = jax.tree.map(jnp.copy, trainable)
            keep_s = jax.tree.map(jnp.copy, state)
            _, _, metrics = step(params, trainable, state, batch)
            return keep_t, keep_s, metrics
        return broken
    return _wrap_train_step(wrap)


def half_batch() -> Callable[[], None]:
    """The step sees only the first half of the batch's tokens: the rest
    are masked out and the loss is the mean over those it keeps."""
    import jax.numpy as jnp

    def wrap(step):
        def broken(params, trainable, state, batch):
            mask = batch["mask"]
            flat = jnp.arange(mask.size).reshape(mask.shape)
            half = dict(batch, mask=jnp.where(flat < mask.size // 2,
                                              mask, 0.0))
            return step(params, trainable, state, half)
        return broken
    return _wrap_train_step(wrap)


FAULTS = {"token_altered": token_altered, "state_unchanged": state_unchanged,
          "half_batch": half_batch}

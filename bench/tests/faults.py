"""Faults planted in the program underneath a run, for the check to catch.

``planted(name)`` patches one program function for the duration of a
``with`` block:

- ``state_unchanged``: the train step returns its parameters and state as
  it received them;
- ``half_batch``: the train exchange averages only the first half of the
  clients' gradients;
- ``mean_scaled``: the train exchange returns its decoded mean times 1.1,
  as a wrong beta (the estimator's unbiasing constant) or 1/n would;
- ``fl_half``: each FL round decodes only the first half of its clients;
- ``answer_altered``: every decoded mean has its largest coordinate negated
  where the decoder produces it;
- ``fl_state_unchanged``: the FL task's step returns the state unchanged.
"""
from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp


def _state_unchanged(mp):
    import repro.train as train

    orig = train.make_train_step

    def make(*a, **kw):
        step = orig(*a, **kw)

        def broken(params, state, batch, t):
            return params, state, step(params, state, batch, t)[2]
        return broken
    mp.setattr(train, "make_train_step", make)


def _half_batch(mp):
    from repro.dist import collectives

    orig = collectives.compressed_mean_tree

    def half(spec, key, tree, *a, **kw):
        return orig(spec, key, jax.tree.map(lambda g: g[: g.shape[0] // 2], tree), *a, **kw)
    mp.setattr(collectives, "compressed_mean_tree", half)


def _mean_scaled(mp):
    from repro.dist import collectives

    orig = collectives.compressed_mean_tree

    def scaled(*a, **kw):
        mean, info, ef = orig(*a, **kw)
        return jax.tree.map(lambda g: g * 1.1, mean), info, ef
    mp.setattr(collectives, "compressed_mean_tree", scaled)


def _fl_half(mp):
    from repro.fl import rounds

    orig = rounds._group_local

    def half(pipe_g, key, xs_chunks, ids_g, *a, **kw):
        return orig(pipe_g, key, xs_chunks, ids_g[: len(ids_g) // 2], *a, **kw)
    mp.setattr(rounds, "_group_local", half)


def _answer_altered(mp):
    from repro.core.codec.pipeline import Pipeline

    orig = Pipeline.decode

    def altered(self, *a, **kw):
        out = orig(self, *a, **kw)
        flat = out.reshape(-1)
        return flat.at[jnp.argmax(jnp.abs(flat))].multiply(-1.0).reshape(out.shape)
    mp.setattr(Pipeline, "decode", altered)


def _fl_state_unchanged(mp):
    import dataclasses

    from repro.fl import tasks

    orig = tasks.power_iteration

    def frozen(*a, **kw):
        return dataclasses.replace(orig(*a, **kw), step=lambda state, mean: state)
    mp.setattr(tasks, "power_iteration", frozen)


FAULTS = {
    "none": lambda mp: None,
    "state_unchanged": _state_unchanged,
    "half_batch": _half_batch,
    "mean_scaled": _mean_scaled,
    "fl_half": _fl_half,
    "answer_altered": _answer_altered,
    "fl_state_unchanged": _fl_state_unchanged,
}


@contextlib.contextmanager
def planted(name: str):
    import pytest

    with pytest.MonkeyPatch.context() as mp:
        FAULTS[name](mp)
        yield

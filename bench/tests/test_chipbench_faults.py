"""A run with the timed path broken underneath comes out not correct.

Each case drives the rest of a run (set-up, window, reference check) on
the CPU at a small size, past the harness's look for a chip, with one
fault planted in the program: a step that returns its state unchanged,
half of the batch left out with the sum scaled up to hide it (in the
step, and in the validation posterior), a loss altered where it is
produced, and validation answers altered where they are produced (on
kegg-train, whose limits hold the validation mean). The cells run on one
chip, so no exchange between chips can be left out.
"""
import time

import jax
import pytest

from bench import harness

SCALE = 0.02


def run(cell_name, seconds=0.5):
    spec = harness.load_spec()
    cell = harness.find_cell(spec, cell_name)
    return harness.run_cell(
        spec, cell, seed=7, seconds=seconds, trace=False,
        devices=jax.devices(),
        peak=harness.load_peaks()["devices"]["TPU v5 lite"],
        t_start=time.perf_counter(), scale=SCALE)


def state_unchanged(monkeypatch):
    from repro.optim import Adam
    monkeypatch.setattr(Adam, "update",
                        lambda self, grads, state, params: (params, state))


def half_batch(monkeypatch):
    import repro.gp
    real = repro.gp.mll_value_and_grad

    def half(model, params, x, y, key, **kw):
        h = x.shape[0] // 2
        res = real(model, params, x[:h], y[:h], key, **kw)
        twice = jax.tree.map(lambda g: 2 * g, res.grads)
        return res._replace(mll=2 * res.mll, grads=twice)

    monkeypatch.setattr(repro.gp, "mll_value_and_grad", half)


def loss_altered(monkeypatch):
    import repro.gp
    real = repro.gp.mll_value_and_grad

    def altered(*args, **kw):
        res = real(*args, **kw)
        return res._replace(mll=res.mll * (1 + 1e-2))

    monkeypatch.setattr(repro.gp, "mll_value_and_grad", altered)


def val_half_batch(monkeypatch):
    import repro.gp
    real = repro.gp.posterior

    def half(model, params, x, y, xs, **kw):
        h = x.shape[0] // 2
        return real(model, params, x[:h], y[:h], xs, **kw)

    monkeypatch.setattr(repro.gp, "posterior", half)


def val_answer_altered(monkeypatch):
    import repro.gp
    real = repro.gp.posterior

    def altered(*args, **kw):
        post = real(*args, **kw)
        return post._replace(mean=post.mean * 1.1)

    monkeypatch.setattr(repro.gp, "posterior", altered)


@pytest.mark.parametrize("cell_name,fault", [
    ("protein-train", state_unchanged),
    ("protein-train", half_batch),
    ("protein-train", loss_altered),
    ("kegg-train", val_half_batch),
    ("kegg-train", val_answer_altered),
])
def test_a_broken_timed_path_is_not_correct(monkeypatch, cell_name, fault):
    fault(monkeypatch)
    out = run(cell_name)
    assert out["correct"] is False, out["checks"]

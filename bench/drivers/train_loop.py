"""Driver ``train_loop``: full-batch fit epochs, as ``gp.train.fit`` runs them.

One epoch is the jitted training step (``mll_value_and_grad`` and Adam,
with fit's host reads: the pack and capacity overflow flags, the MLL and
the finite-gradient flag) followed by the validation posterior and its
RMSE read. Caps are sized in set-up as ``fit`` sizes them: one auto build
at the initial hyperparameters, times the headroom. Every
``reset_every`` epochs the parameters and Adam's state go back to their
initial values, so that a window measures the same work on every commit
(a drifting fit would make later epochs costlier and grow the caps).

Set-up runs the first ``warm_steps`` steps through the window's own step
program, the first of them with its validation (which compiles both),
and keeps what they produced for the correctness check: each step's MLL,
the first gradient as Adam holds it after one step, the parameters
before each step and after the last, and the first epoch's validation
posterior mean. After the window, the plain reference in
``bench/reference`` checks them (``Reference``).

Traffic parameters: ``lr``, ``reset_every``, ``warm_steps``,
``val_variance_rank``, ``cap_headroom``.
"""
from __future__ import annotations

import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import workload
from bench.reference import gp as ref
from bench.reference import permutohedral


def _leaf_norms(tree) -> dict:
    return {k: float(np.linalg.norm(np.asarray(v, np.float64)))
            for k, v in tree.items()}


def _leaves(params) -> dict:
    return {"raw_lengthscale": params.raw_lengthscale,
            "raw_outputscale": params.raw_outputscale,
            "raw_noise": params.raw_noise}


def _leaf_values(params) -> dict:
    return {k: np.asarray(v, np.float64) for k, v in _leaves(params).items()}


def _ref_params(leaves: dict) -> ref.Params:
    return ref.Params(np.asarray(leaves["raw_lengthscale"], np.float64),
                      float(np.ravel(leaves["raw_outputscale"])[0]),
                      float(np.ravel(leaves["raw_noise"])[0]))


class Driver:
    def __init__(self, config: dict, traffic: dict, seed: int, log,
                 scale: float = 1.0):
        from repro.core.lattice import build_lattice_auto, default_capacity
        from repro.gp import GPParams, mll_value_and_grad, posterior, rmse
        from repro.optim import Adam

        self.config, self.traffic, self.seed, self.log = config, traffic, seed, log
        ds = workload.split(config, seed, scale)
        self.ds = ds
        model = workload.gp_model(config)
        self.model = model
        x, y = jnp.asarray(ds.x_train), jnp.asarray(ds.y_train)
        xv, yv = jnp.asarray(ds.x_val), jnp.asarray(ds.y_val)
        n, d = ds.x_train.shape
        self.params0 = GPParams.init(d)
        opt = Adam(learning_rate=traffic["lr"])
        self.state0 = opt.init(self.params0)

        # caps as fit sizes them: one auto build, times the headroom
        st = model.stencil
        ls0 = model.constrained(self.params0)[0]
        head = traffic["cap_headroom"]

        def cap_for(pts):
            lat = build_lattice_auto(pts / ls0[None, :], spacing=st.spacing,
                                     r=st.r, backend=model.config.build_backend)
            worst = default_capacity(*pts.shape)
            return min(max(lat.cap * head, 1024), worst), int(lat.m)

        self.cap, self.m = cap_for(x)
        self.cap_val, self.m_val = cap_for(jnp.concatenate([x, xv]))
        log(f"train_loop: n={n} d={d} n_val={xv.shape[0]} m={self.m} "
            f"cap={self.cap} m_val={self.m_val} cap_val={self.cap_val}")

        # the data are arguments, not constants baked into the programs, so
        # the compile cache serves every seed's split
        @jax.jit
        def step(params, opt_state, key, x, y):
            res = mll_value_and_grad(model, params, x, y, key, cap=self.cap)
            grads_ok = jnp.all(jnp.asarray([jnp.all(jnp.isfinite(g))
                                            for g in jax.tree.leaves(res.grads)]))
            new_params, new_state = opt.update(res.grads, opt_state, params)
            return (new_params, new_state, res.mll, res.cg_iters,
                    res.overflow, res.pack_overflow, grads_ok)

        rank = traffic["val_variance_rank"]

        @jax.jit
        def val(params, key, x, y, xv, yv):
            post = posterior(model, params, x, y, xv, key=key,
                             variance_rank=rank, cap=self.cap_val)
            return rmse(post, yv), post.mean, post.overflow, post.pack_overflow

        self.step_fn, self.val_fn = step, val
        self.data = (x, y, xv, yv)
        self.spans = workload.Spans()
        self.key = jax.random.PRNGKey(workload.sub_seed(seed, "train"))
        self.params, self.opt_state = self.params0, self.state0
        self.in_cycle = 0
        self.epochs = self.failed = 0
        self.cg_iters = None

        # warm steps: compile, and keep what the check compares; only the
        # first validates (the check compares its mean; validation leaves
        # the state alone)
        self.keys, mlls, path, val_mean = [], [], [], None
        for i in range(traffic["warm_steps"]):
            path.append(_leaf_values(self.params))
            out = self.epoch(validate=i == 0)
            mlls.append(out["mll"])
            if i == 0:
                first_grad = {k: np.asarray(v, np.float64) / (1.0 - opt.b1)
                              for k, v in _leaves(self.opt_state.mu).items()}
                val_mean = np.asarray(out["mean"], np.float64)
        path.append(_leaf_values(self.params))
        self.got = {"mlls": mlls, "first_grad": first_grad, "path": path,
                    "val_mean": val_mean}
        self.warm_failed = self.failed

    def epoch(self, validate: bool = True) -> dict:
        """One epoch: the step with fit's host reads, then validation."""
        self.key, k1, k2 = jax.random.split(self.key, 3)
        if len(self.keys) < self.traffic["warm_steps"]:
            self.keys.append(k1)
        with self.spans("step"):
            x, y, xv, yv = self.data
            out = self.step_fn(self.params, self.opt_state, k1, x, y)
            new_params, new_state, mll, iters, ovf, povf, gok = out
            bad = bool(povf) or bool(ovf)
            mll = float(mll)
            bad = bad or not math.isfinite(mll) or not bool(gok)
        if not bad:
            self.params, self.opt_state = new_params, new_state
        self.cg_iters = int(iters)
        r = mean = None
        if validate:
            with self.spans("validation"):
                r, mean, vovf, vpovf = self.val_fn(self.params, k2, x, y, xv, yv)
                r = float(r)
                bad = bad or bool(vovf) or bool(vpovf) or not math.isfinite(r)
        self.epochs += 1
        self.failed += int(bad)
        self.in_cycle += 1
        if self.in_cycle == self.traffic["reset_every"]:
            self.params, self.opt_state, self.in_cycle = (self.params0,
                                                          self.state0, 0)
        return {"mll": mll, "rmse": r, "mean": mean}

    def window(self, seconds: float) -> dict:
        e0, f0 = self.epochs, self.failed
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            self.epoch()
        window_s = time.perf_counter() - t0
        done = self.epochs - e0
        spans = [s for s in self.spans.items if s[1] >= t0]
        secs = {name: " ".join(f"{t1 - t0_:.3f}" for n, t0_, t1 in spans
                               if n == name)
                for name in ("step", "validation")}
        return {"window_s": window_s, "attempted": done,
                "failed": self.failed - f0,
                "metrics": {"epoch_s": window_s / done},
                "spans": spans,
                "counters": {"epochs": done, "cg_iters": self.cg_iters},
                "notes": [f"train_loop: epochs={done} "
                          f"warm_failed={self.warm_failed}",
                          f"train_loop: step_s {secs['step']}",
                          f"train_loop: validation_s {secs['validation']}"]}

    def objects(self) -> dict:
        """What the per-layer readers measure against."""
        n, d = self.ds.x_train.shape
        return {"model": self.model, "x": jnp.asarray(self.ds.x_train),
                "params0": self.params0, "cap": self.cap, "m": self.m,
                "n": n, "d": d, "c": 1 + self.model.config.num_probes,
                "cg_iters": self.cg_iters}

    # -- correctness ---------------------------------------------------------

    def release(self):
        self.step_fn = self.val_fn = self.data = None
        self.params = self.opt_state = None

    def check(self) -> dict:
        """Frees the program's state, runs the reference, and returns the
        readings the cell's limits apply to."""
        self.release()
        self.reference = Reference(self.config["model"], self.traffic["lr"],
                                   self.ds, self.keys)
        return dict(self.reference.readings(self.got),
                    warm_failed=self.warm_failed)

    def stand_in(self, kind: str) -> dict:
        """Readings of the reference put in the program's place, after
        ``check``: ``control`` (bfloat16 lattice tables) or the fault
        ``half_batch``."""
        r = self.reference
        run = (r.run(store=permutohedral.bf16, validate=True)
               if kind == "control" else r.run(half=True, validate=True))
        return dict(r.readings(run), warm_failed=0)


class Reference:
    """The plain reference's side of the check, for one split and the
    keys of the warm steps.

    Each step's loss and the validation mean are read at the parameters
    the checked run itself held at that step: under Adam a gradient
    component near zero moves its parameter by a whole learning rate
    either way, so the rounding of the first steps moves the later
    parameters, and the loss there is a steep function of them. The first
    gradient and the parameters' change after the warm steps are read
    against the reference's own steps."""

    def __init__(self, model: dict, lr: float, ds, keys):
        self.model, self.lr, self.ds, self.keys = model, lr, ds, keys
        self.own = self.run()

    def run(self, store=None, half: bool = False,
            validate: bool = False) -> dict:
        """The reference's own warm steps. ``store`` lowers its lattice
        tables' precision (the control); ``half`` leaves out the second
        half of the training batch and doubles the MLL and gradients of
        the rest (a fault, planted in the reference)."""
        gp = ref.SimplexGP(self.model, store=store)
        x, y = self.ds.x_train, self.ds.y_train
        k = 2 if half else 1
        if half:
            x, y = x[:x.shape[0] // 2], y[:y.shape[0] // 2]
        p = ref.Params.init(x.shape[1])
        state = {"t": 0}
        mlls, scales, path = [], [], []
        for i, key in enumerate(self.keys):
            path.append(p.leaves())
            mll, scale, g = gp.mll_step(p, x, y, key)
            g = ref.Params(k * g.raw_ls, k * g.raw_os, k * g.raw_noise)
            mlls.append(k * mll)
            scales.append(k * scale)
            if i == 0:
                first = g.leaves()
            p, state = ref.adam(p, g, state, self.lr)
        path.append(p.leaves())
        out = {"mlls": mlls, "scales": scales, "first_grad": first,
               "path": path}
        if validate:
            out["val_mean"] = gp.posterior_mean(_ref_params(path[1]), x, y,
                                                self.ds.x_val)
        return out

    def at(self, path: list) -> dict:
        """The sound loss of each warm step at the parameters in ``path``
        (the first step starts from the initial parameters, as the
        reference's own does) and the validation mean after the first."""
        gp = ref.SimplexGP(self.model)
        x, y = self.ds.x_train, self.ds.y_train
        mlls, scales = self.own["mlls"][:1], self.own["scales"][:1]
        for key, leaves in zip(self.keys[1:], path[1:]):
            mll, scale, _ = gp.mll_step(_ref_params(leaves), x, y, key)
            mlls.append(mll)
            scales.append(scale)
        return {"mlls": mlls, "scales": scales,
                "val_mean": gp.posterior_mean(_ref_params(path[1]), x, y,
                                              self.ds.x_val)}

    def readings(self, got: dict) -> dict:
        """A run's warm steps (``mlls``, ``first_grad``, ``path``,
        ``val_mean``) against the reference."""
        at = self.at(got["path"])
        ref_g = _leaf_norms(self.own["first_grad"])
        med = float(np.median(list(ref_g.values())))
        # leaves the reference leaves unmoved (gradient nought to rounding)
        quiet = {k for k, v in ref_g.items() if v < 1e-3 * med}

        def change(path):
            return {k: np.asarray(path[-1][k], np.float64)
                    - np.asarray(path[0][k], np.float64) for k in path[0]}

        return {
            # each step's loss, against the magnitude of its terms: read
            # against the MLL itself the gap swings where a step's MLL
            # passes near zero
            "loss_gap": max(abs(a - b) / s for a, b, s in
                            zip(got["mlls"], at["mlls"], at["scales"])),
            "grad_gap": workload.leaf_gaps(_leaf_norms(got["first_grad"]),
                                           ref_g),
            "change_gap": workload.leaf_gaps(
                _leaf_norms(change(got["path"])),
                _leaf_norms(change(self.own["path"])), skip=quiet),
            # the first epoch's validation posterior mean, point by point
            "val_mean_gap": workload.rel_norm_gap(got["val_mean"],
                                                  at["val_mean"]),
        }

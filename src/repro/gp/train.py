"""Full-batch GP hyperparameter training (paper §5.3, Appendix A).

Adam(lr=0.1) on the BBMM MLL; CG tolerance 1.0 during training and 1e-2 at
eval; early stopping on *validation RMSE* (§5.4: the MLL is non-monotone at
high CG tolerance, so the best model is selected by held-out RMSE). Optional
RR-CG solves reproduce Table 4's stability/runtime trade-off.

Lattice sizing (DESIGN.md §9): the jitted step needs a STATIC table
capacity, but the worst case n(d+1) over-allocates ~3-50x on real data
(paper Table 3) and every per-lattice-point array — the neighbor table
above all — scales with it. So ``fit`` right-sizes the cap OUTSIDE jit
with ``build_lattice_auto`` under the initial hyperparameters (plus
headroom for lengthscale drift), threads it into the jitted step/eval as a
static argument, and watches the step's overflow flag: if training moves
the lengthscale enough to overflow the table, the cap grows and the step
re-jits — the grow-and-retry contract, amortized over the whole run.

Durability (DESIGN.md §14): training state is the expensive asset of an
MVM-based run, so ``fit`` periodically checkpoints the FULL loop state —
``(params, opt_state, best_params, rng key)`` as host-gathered logical
arrays via ``runtime/checkpoint.py`` (so a restore re-shards onto any
mesh, per ``runtime/elastic.py``), plus the non-array loop state (epoch,
caps, early-stop bookkeeping, the divergence window) in the manifest.
A crashed run re-invoked with the same ``ckpt_dir`` resumes from the
newest VALID checkpoint bit-compatibly: the rng key is saved post-split,
so the resumed trajectory is the uninterrupted one.

The same snapshot powers the DIVERGENCE GUARD: a non-finite loss/grad or
a loss spike outside the windowed band rolls the loop back to the last
good state (in-memory; the disk checkpoint is the crash-durable copy)
with escalated noise jitter and a backed-off learning rate — bounded by
``max_rollbacks``, every event recorded in the ``FitReport``.
"""
from __future__ import annotations

import dataclasses
import math
import time
from typing import Callable

import jax
import jax.numpy as jnp

from repro import obs
from repro.core.lattice import build_lattice_auto, default_capacity
from repro.gp import mll as mll_mod
from repro.gp import predict as predict_mod
from repro.gp.models import GPParams, SimplexGP
from repro.optim import Adam
from repro.runtime import faults as faults_mod
from repro.runtime.checkpoint import CheckpointManager

Array = jax.Array

CAP_GROWTH = 4  # multiplier applied when a step/eval overflows its table


@dataclasses.dataclass
class FitReport:
    """Durability/robustness log of one ``fit`` run (DESIGN.md §14)."""

    resumed_from_epoch: int | None = None  # checkpointed epoch restored at start
    checkpoint_dir: str | None = None
    checkpoints_written: int = 0
    rollbacks: list = dataclasses.field(default_factory=list)
    # each rollback entry: {epoch, reason, restored_epoch, lr_scale,
    #                       jitter_raw} — the full escalation trail
    completed_epochs: int = 0
    retries: list = dataclasses.field(default_factory=list)
    # each retry entry: {epoch, error, remaining} — a transient in-step
    # failure that was absorbed by re-running the step (DESIGN.md §16)
    watchdog_breaches: list = dataclasses.field(default_factory=list)
    # each breach entry: {epoch, deadline, seconds} — a slow/hung step
    # that tripped the StepWatchdog; fit checkpoints immediately after
    interrupted: str | None = None  # why the loop stopped early, if it did


@dataclasses.dataclass
class TrainResult:
    params: GPParams
    best_params: GPParams
    history: list[dict]
    best_val_rmse: float
    report: FitReport = dataclasses.field(default_factory=FitReport)


def _auto_cap(model: SimplexGP, params: GPParams, x: Array, *,
              headroom: int = 2) -> int:
    """Right-size a static lattice capacity for ``x`` under ``params``.

    One eager auto build (grow-and-retry on the overflow flag), then
    ``headroom``x margin so moderate lengthscale shrink during training
    does not immediately overflow the table.
    """
    st = model.stencil
    ls = model.constrained(params)[0]
    lat = build_lattice_auto(x / ls[None, :], spacing=st.spacing, r=st.r,
                             backend=model.config.build_backend)
    worst = default_capacity(*x.shape)
    return min(max(lat.cap * headroom, 1024), worst)


@dataclasses.dataclass
class _LoopState:
    """Everything the loop needs to continue from — the checkpoint unit."""

    params: GPParams
    opt_state: object
    best_params: GPParams
    key: Array
    epoch: int  # last COMPLETED epoch (-1 = none)
    cap: int
    cap_val: int
    best_val_rmse: float
    stall: int
    lr_scale: float
    jitter_raw: float
    window: list  # recent accepted losses (-mll) for the spike band
    rollbacks: list  # rollback log entries (survive resume)

    def arrays(self) -> dict:
        return {"params": self.params, "opt_state": self.opt_state,
                "best_params": self.best_params, "key": self.key}

    def extra(self) -> dict:
        return {"epoch": self.epoch, "cap": self.cap,
                "cap_val": self.cap_val,
                "best_val_rmse": self.best_val_rmse, "stall": self.stall,
                "lr_scale": self.lr_scale, "jitter_raw": self.jitter_raw,
                "window": list(self.window),
                "rollbacks": list(self.rollbacks)}


def fit(model: SimplexGP, x: Array, y: Array, *, x_val: Array, y_val: Array,
        epochs: int = 100, lr: float = 0.1, seed: int = 0,
        use_rrcg: bool = False, patience: int = 15,
        auto_cap: bool = True, mesh=None,
        log_fn: Callable[[str], None] | None = None,
        ckpt_dir: str | None = None, ckpt_every: int = 10,
        keep_last: int = 3, resume: bool = True,
        max_rollbacks: int = 3, spike_window: int = 8,
        spike_sigma: float = 10.0, lr_backoff: float = 0.5,
        jitter_raw0: float = 0.1, faults=None,
        step_retries: int = 2, watchdog=None,
        watchdog_abort: bool = False) -> TrainResult:
    """``mesh`` runs every solve/posterior MVM data-parallel over the
    mesh's "data" axis (DESIGN.md §10); n and n + n_val must divide the
    axis size. The lattice build and the surrogate gradients stay
    single-device — the per-iteration MVMs are where the time goes.

    Durability knobs: ``ckpt_dir`` enables crash-durable checkpoints
    every ``ckpt_every`` epochs (atomic, async, ``keep_last`` retained
    plus keep-best by validation RMSE); re-invoking ``fit`` with the same
    ``ckpt_dir`` and ``resume=True`` continues from the newest VALID
    checkpoint (corrupt generations are skipped) with the identical rng
    trajectory. The divergence guard rolls back to the last good state
    when the loss/grads go non-finite or the loss spikes more than
    ``spike_sigma`` standard deviations above the ``spike_window``-epoch
    band, escalating a raw-noise jitter (+``jitter_raw0`` · 2^k) and
    backing off the learning rate (×``lr_backoff``) each time; after
    ``max_rollbacks`` rollbacks it raises rather than looping. ``faults``
    (a ``runtime/faults.FaultInjector``) arms the scripted crash/
    divergence probes the recovery tests replay.

    Elastic/failure semantics (DESIGN.md §16): a transient exception
    raised INSIDE the jitted step (the ``"fit_step"`` fault site, or any
    error ``runtime/faults.is_injected`` recognizes) is absorbed by
    re-running the step — up to ``step_retries`` consecutive times per
    epoch, each recorded in ``FitReport.retries`` — because the step is
    a pure function of ``(params, opt_state, key)``: nothing was mutated
    when it raised, so the retry replays the identical computation. A
    ``watchdog`` (``runtime/straggler.StepWatchdog``) times every epoch;
    a breach is recorded in ``FitReport.watchdog_breaches`` and forces
    an immediate checkpoint (the epoch's result is still valid — slow is
    not wrong), and with ``watchdog_abort=True`` the loop then returns
    early with ``FitReport.interrupted = "watchdog_breach"`` so an
    elastic supervisor (launch/elastic_gp.py) can re-shard onto a
    surviving mesh and resume from that checkpoint.
    """
    d = x.shape[1]
    worst = default_capacity(*x.shape)
    worst_joint = default_capacity(x.shape[0] + x_val.shape[0], d)

    manager = None
    if ckpt_dir is not None:
        manager = CheckpointManager(ckpt_dir, keep_last=keep_last,
                                    keep_best=1)

    report = FitReport(checkpoint_dir=ckpt_dir)

    # -- initial or resumed loop state --------------------------------------
    def _fresh_state() -> _LoopState:
        params = GPParams.init(d)
        if auto_cap and model.config.shared_lattice:
            cap = _auto_cap(model, params, x)
            cap_val = _auto_cap(model, params, jnp.concatenate([x, x_val]))
        else:
            cap, cap_val = worst, worst_joint
        return _LoopState(params=params,
                          opt_state=Adam(learning_rate=lr).init(params),
                          best_params=params,
                          key=jax.random.PRNGKey(seed), epoch=-1,
                          cap=cap, cap_val=cap_val,
                          best_val_rmse=float("inf"), stall=0,
                          lr_scale=1.0, jitter_raw=0.0, window=[],
                          rollbacks=[])

    st = _fresh_state()
    if manager is not None and resume:
        step0 = manager.latest_valid_step()
        if step0 is not None:
            tmpl = st.arrays()
            tree = manager.restore(step0, jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tmpl))
            extra = manager.manifest(step0)["extra"]
            st = _LoopState(params=tree["params"],
                            opt_state=tree["opt_state"],
                            best_params=tree["best_params"],
                            key=tree["key"], epoch=int(extra["epoch"]),
                            cap=int(extra["cap"]),
                            cap_val=int(extra["cap_val"]),
                            best_val_rmse=float(extra["best_val_rmse"]),
                            stall=int(extra["stall"]),
                            lr_scale=float(extra["lr_scale"]),
                            jitter_raw=float(extra["jitter_raw"]),
                            window=list(extra.get("window", [])),
                            rollbacks=list(extra.get("rollbacks", [])))
            report.resumed_from_epoch = st.epoch
            if log_fn:
                log_fn(f"resume: restored epoch {st.epoch} from {ckpt_dir}")

    def make_opt(lr_scale: float) -> Adam:
        return Adam(learning_rate=lr * lr_scale)

    opt = make_opt(st.lr_scale)

    def make_step(cap, opt):
        # the in-step fault probe is only traced in when an injector is
        # armed: the production step (faults=None) compiles the identical
        # program it always did, so the PR 7 bit-compatibility guarantee
        # is untouched. The guarded variant takes a host-planned fault
        # code as an operand and returns the callback's poison flag as an
        # EXTRA OUTPUT (outputs cannot be dead-code-eliminated), leaving
        # mll/params untouched — guarded and unguarded trajectories stay
        # bit-identical. The callback only sleeps/echoes, never raises:
        # raising from one device thread of a sharded program deadlocks
        # the others in the collective (faults.exec_step_fault).
        guarded = faults is not None
        @jax.jit
        def step(params, opt_state, key, fault_code=None):
            res = mll_mod.mll_value_and_grad(model, params, x, y, key,
                                             use_rrcg=use_rrcg, cap=cap,
                                             mesh=mesh)
            grads_ok = jnp.all(jnp.asarray(
                [jnp.all(jnp.isfinite(g))
                 for g in jax.tree.leaves(res.grads)]))
            new_params, new_state = opt.update(res.grads, opt_state, params)
            out = (new_params, new_state, res.mll, res.cg_iters,
                   res.overflow, res.pack_overflow, grads_ok)
            if guarded:
                probe = jax.pure_callback(
                    faults_mod.exec_step_fault,
                    jax.ShapeDtypeStruct((), jnp.float32), fault_code)
                out = out + (probe,)
            return out
        return step

    def make_val(cap_val):
        @jax.jit
        def val_rmse(params, key):
            post = predict_mod.posterior(model, params, x, y, x_val,
                                         key=key, variance_rank=10,
                                         cap=cap_val, mesh=mesh)
            return (predict_mod.rmse(post, y_val), post.overflow,
                    post.pack_overflow, post.cg_iters)
        return val_rmse

    def _check_pack(povf):
        # coordinate-range overflow corrupts results and no capacity can
        # fix it — fail loudly rather than train on a broken lattice
        if bool(povf):
            raise RuntimeError(
                "lattice coordinate range overflow (|coord| > 2^15): the "
                "lengthscale/input scaling is degenerate (z = x / ls far "
                "too spread). Rescale inputs or bound the lengthscale.")

    step = make_step(st.cap, opt)
    val_rmse = make_val(st.cap_val)

    # in-memory rollback anchor: a cheap host copy of the last GOOD state
    # (the disk checkpoint is the crash-durable copy of the same thing)
    good = jax.tree.map(jnp.asarray, st.arrays())
    good_meta = st.extra()

    def _spike(loss: float) -> bool:
        w = st.window
        if len(w) < spike_window or not math.isfinite(loss):
            return False
        mean = sum(w) / len(w)
        var = sum((v - mean) ** 2 for v in w) / len(w)
        band = spike_sigma * max(math.sqrt(var),
                                 0.02 * abs(mean) + 1e-3)
        return loss > mean + band

    def _rollback(epoch: int, reason: str):
        nonlocal opt, step, good, good_meta
        with obs.span("rollback", epoch=epoch, reason=reason):
            if len(st.rollbacks) >= max_rollbacks:
                raise RuntimeError(
                    f"fit: divergence guard exhausted after {max_rollbacks} "
                    f"rollback(s); last reason: {reason}")
            restored = jax.tree.map(jnp.asarray, good)
            st.params = restored["params"]
            st.opt_state = restored["opt_state"]
            st.best_params = restored["best_params"]
            st.key = restored["key"]
            st.epoch = int(good_meta["epoch"])
            st.best_val_rmse = float(good_meta["best_val_rmse"])
            st.stall = int(good_meta["stall"])
            st.window = []  # post-restore losses rejoin a fresh band
            st.lr_scale *= lr_backoff
            st.jitter_raw = jitter_raw0 * (2 ** len(st.rollbacks))
            # escalated jitter: a larger noise floor conditions K_hat better;
            # raw-space additive keeps the bump monotone under softplus
            st.params = dataclasses.replace(
                st.params, raw_noise=st.params.raw_noise + st.jitter_raw)
            entry = dict(epoch=epoch, reason=reason,
                         restored_epoch=st.epoch, lr_scale=st.lr_scale,
                         jitter_raw=st.jitter_raw)
            st.rollbacks.append(entry)
            report.rollbacks.append(entry)
            opt = make_opt(st.lr_scale)
            step = make_step(st.cap, opt)
            if log_fn:
                log_fn(f"rollback #{len(st.rollbacks)} at epoch {epoch} "
                       f"({reason}): restored epoch {st.epoch}, "
                       f"lr x{st.lr_scale:g}, jitter +{st.jitter_raw:g}")


    def _checkpoint(metric: float | None):
        if manager is None:
            return
        with obs.span("checkpoint", epoch=st.epoch):
            manager.save(st.epoch, st.arrays(), metric=metric,
                         extra=st.extra())
        report.checkpoints_written += 1

    report.rollbacks.extend(st.rollbacks)
    history = []
    epoch = st.epoch + 1
    while epoch < epochs:
        with obs.span("epoch", epoch=epoch):
            if faults is not None:
                faults.kill_if_armed("fit")  # scripted device loss (os._exit)
                faults.maybe_raise("fit")  # scripted crash (recovery tests)
                if faults.take("fit", "nan_params") is not None:
                    st.params = dataclasses.replace(
                        st.params, raw_lengthscale=st.params.raw_lengthscale
                        .at[0].set(jnp.nan))
                if faults.take("fit", "spike_params") is not None:
                    # near-zero noise: K_hat goes ill-conditioned and the
                    # data-fit term y^T K^-1 y explodes — a reliable, finite
                    # loss spike (unlike outputscale, whose logdet blow-up
                    # the truncated SLQ estimate underreports)
                    st.params = dataclasses.replace(
                        st.params, raw_noise=st.params.raw_noise - 18.0)
            st.key, k1, k2 = jax.random.split(st.key, 3)
            t0 = time.perf_counter()
            pre_breaches = 0 if watchdog is None else len(watchdog.breaches)
            if watchdog is not None:
                watchdog.start_step(epoch)
            retries_left = step_retries
            while True:
                try:
                    with obs.span("step"):
                        if faults is not None:
                            # consume the in-step schedule ONCE per
                            # dispatch (a retry is a new dispatch) and hand
                            # the decision to the compiled step as an
                            # operand; block so the injected sleep/poison
                            # has materialized before the flag is
                            # inspected, then raise the scripted fault HERE
                            # on the host — the callback itself never raises
                            code = faults.plan_step("fit_step")
                            out = jax.block_until_ready(
                                step(st.params, st.opt_state, k1, code))
                            *out, probe = out
                            if float(probe) != 0.0:
                                raise faults_mod.InjectedFault(
                                    "injected exception at 'fit_step'")
                        else:
                            out = jax.block_until_ready(
                                step(st.params, st.opt_state, k1))
                    new_params, new_state, mll, iters, ovf, povf, gok = out
                except Exception as err:  # noqa: BLE001 — others re-raised
                    if (retries_left > 0 and faults is not None
                            and faults_mod.is_injected(err)):
                        # the step's outputs are discarded on the poison
                        # path and nothing host-side was mutated —
                        # re-running it is safe and (fault aside) replays
                        # the identical computation
                        retries_left -= 1
                        entry = dict(epoch=epoch,
                                     error=str(err).splitlines()[0][:200],
                                     remaining=retries_left)
                        report.retries.append(entry)
                        if log_fn:
                            log_fn(f"transient step failure at epoch "
                                   f"{epoch}: retrying ({retries_left} retr"
                                   f"{'y' if retries_left == 1 else 'ies'} "
                                   "left)")
                        continue
                    raise
                with obs.span("step.read") as attrs:
                    _check_pack(povf)
                    overflowed = bool(ovf)
                    mll, iters, gok = float(mll), int(iters), bool(gok)
                    attrs["cg_iters"] = iters
                if not overflowed or st.cap >= worst:
                    break
                st.cap = min(st.cap * CAP_GROWTH, worst)  # stale grads: regrow
                with obs.span("regrow", cap=st.cap):
                    step = make_step(st.cap, opt)
            breached = False
            if watchdog is not None:
                step_seconds = time.perf_counter() - t0
                watchdog.end_step(step_seconds)
                breached = len(watchdog.breaches) > pre_breaches
                if breached:
                    report.watchdog_breaches.append(dict(
                        epoch=epoch, deadline=watchdog.breaches[-1][1],
                        seconds=step_seconds))
                    if log_fn:
                        log_fn(f"watchdog breach at epoch {epoch}: step took "
                               f"{step_seconds:.2f}s (deadline "
                               f"{watchdog.breaches[-1][1]:.2f}s)")

            # -- divergence guard (DESIGN.md §14) ---------------------------
            loss = -mll if math.isfinite(mll) else float("nan")
            if not (math.isfinite(mll) and gok):
                _rollback(epoch, "non-finite loss/grads")
                epoch = st.epoch + 1
                continue
            if _spike(loss):
                _rollback(epoch, f"loss spike ({loss:.4g} outside the "
                                 f"{len(st.window)}-epoch band)")
                epoch = st.epoch + 1
                continue

            st.params, st.opt_state = new_params, new_state
            dt = time.perf_counter() - t0
            with obs.span("validation") as attrs:
                while True:
                    rmse_v, ovf, povf, val_iters = val_rmse(st.params, k2)
                    _check_pack(povf)
                    if not bool(ovf) or st.cap_val >= worst_joint:
                        break
                    st.cap_val = min(st.cap_val * CAP_GROWTH, worst_joint)
                    with obs.span("regrow", cap_val=st.cap_val):
                        val_rmse = make_val(st.cap_val)
                rmse, val_iters = float(rmse_v), int(val_iters)
                attrs["cg_iters"] = val_iters
            st.window = (st.window + [loss])[-spike_window:]
            history.append(dict(epoch=epoch, mll=mll, val_rmse=rmse,
                                cg_iters=iters, val_cg_iters=val_iters,
                                seconds=dt, cap=st.cap))
            if log_fn:
                log_fn(f"epoch {epoch:3d}  mll/n {mll / x.shape[0]:+.4f}  "
                       f"val_rmse {rmse:.4f}  cg_iters {iters}  {dt:.2f}s")
            if rmse < st.best_val_rmse - 1e-5:
                st.best_val_rmse = rmse
                st.best_params = st.params
                st.stall = 0
            else:
                st.stall += 1
            st.epoch = epoch
            report.completed_epochs += 1

            # the just-completed epoch is the new rollback anchor (host copy,
            # detached from the loop's live references)
            good = jax.tree.map(jnp.asarray, st.arrays())
            good_meta = st.extra()
            if (epoch + 1) % max(ckpt_every, 1) == 0 or breached:
                # a breach forces an immediate checkpoint: the slow epoch's
                # result is valid (slow is not wrong), and if the mesh is
                # about to shrink this is the state the resume picks up
                _checkpoint(rmse)
            if breached and watchdog_abort:
                report.interrupted = "watchdog_breach"
                break
            if st.stall >= patience:
                break
            epoch += 1

    if manager is not None and history:
        _checkpoint(history[-1]["val_rmse"])  # final state always durable
        manager.wait()
    return TrainResult(params=st.params, best_params=st.best_params,
                       history=history, best_val_rmse=st.best_val_rmse,
                       report=report)

"""Fault-tolerant GP serving engine: hot-swap Predictors under live traffic.

The frozen serving path (gp/serve.py, DESIGN.md §12) made a query cheap;
this module makes it OPERABLE (DESIGN.md §13). The design exploits what
the SKI lineage gives us for free: a Predictor is an immutable pytree of
precomputed tables, so "updating the model" is building a NEW pytree off
the query path, validating it, and atomically publishing it — queries
never lock against refreshes, and a broken candidate is refused before
any query can observe it.

Architecture (one class, three lanes):

  query lane    ``query(xs)`` reads the current Predictor (a single
                Python reference — atomic under the GIL), serves through
                ``gp.serve.predict``, and applies per-request robustness:
                bounded retry with a wall-clock deadline on transient
                failures, an explicit prior-fallback lane for full-miss
                queries, a final finiteness check (the zero-invalid-
                responses guarantee), and rolling miss_mass staleness
                tracking with an alert threshold.

  refresh lane  ``submit_refresh(...)`` records new data; the refresh
                (inline via ``refresh_now`` or on the background worker
                thread) re-freezes via ``gp.serve.refreeze`` — CG warm-
                started from the old alpha, hash index reused when the
                lattice is unchanged — validates the candidate with
                ``serve.validate_predictor``, and only then swaps it into
                the double-buffered registry. Every refresh runs in its
                own guarded thread with a deadline derived from a
                ``runtime/straggler.StepWatchdog`` over past refresh
                durations: a wedged freeze is abandoned (its result can
                never publish), the last-good Predictor keeps serving,
                and health degrades instead of crashing. A capacity-
                overflow refusal from ``freeze`` retries with grown cap.

  health lane   ``health()`` snapshots status/version/staleness/counters
                so an operator (or the soak harness) can watch the engine
                degrade and recover.

Fault injection: pass a ``runtime/faults.FaultInjector`` and the engine
probes it at its sites ("refresh" exceptions, "freeze" slow/NaN/cg-stall/
overflow, "query" transients) — benchmarks/fig_soak.py scripts a failure
schedule through a live engine and asserts zero invalid responses.

Durability (DESIGN.md §14): a ``PredictorStore`` makes the engine's
published state survive the process. The store is a named multi-model
registry on disk (``<root>/<model>/gen_<k>/``, each generation one
atomic ``gp.serve.save_predictor`` directory, keep-last-k plus keep-best
retention). An engine constructed with a store WARM-BOOTS: it serves the
newest generation that passes the full load gate (integrity checksums +
``validate_predictor`` + self-probe), falling back generation by
generation past corrupt ones, and only cold-freezes from the constructor
data when no valid generation exists. Every published Predictor is
persisted POST-publish on a background thread — queries never wait on
disk; a persist failure degrades health, never serving.
"""
from __future__ import annotations

import collections
import dataclasses
import itertools
import json
import pathlib
import threading
import time
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import filtering
from repro.gp.models import GPParams, SimplexGP
from repro.gp.serve import (Predictor, PredictorLoadError, load_predictor,
                            predict, refreeze, freeze, save_predictor,
                            validate_predictor)
from repro.runtime.faults import FaultInjector
from repro.runtime.straggler import StepWatchdog

Array = jax.Array


class ServeUnavailable(RuntimeError):
    """Raised when a query exhausts its retry/deadline budget."""


class RefreshRejected(RuntimeError):
    """A candidate Predictor failed the validation gate (never published)."""


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Serving-engine knobs (all host-side; nothing affects frozen math)."""

    variance_rank: int = 16
    require_converged: bool = True  # validation gate refuses stalled solves
    max_retries: int = 2  # per-query transient-failure retries
    query_timeout_s: float = 10.0  # per-query wall-clock budget
    staleness_window: int = 64  # rolling batches in the miss_mass window
    staleness_alert: float = 0.25  # alert when rolling mean miss exceeds
    fallback_miss: float = 0.999  # per-query prior-fallback threshold
    refresh_min_deadline_s: float = 30.0  # wedge deadline floor
    refresh_deadline_multiplier: float = 5.0  # x median refresh duration
    refresh_max_deadline_s: float | None = None  # cap (tests force wedges)
    cap_growth: int = 4  # lattice-cap growth per overflow retry
    max_cap_retries: int = 3
    registry_size: int = 2  # double-buffered: current + previous


class QueryResult(NamedTuple):
    mean: Array  # (b,)
    var: Array  # (b,) latent-f variance
    miss_mass: Array  # (b,)
    fallback: Array  # (b,) bool: full-miss queries served from the prior
    version: int  # Predictor version that served this batch
    stale: bool  # True when data newer than this version is pending/failed


@dataclasses.dataclass(frozen=True)
class HealthStatus:
    """Point-in-time engine health (all counters monotone since start)."""

    status: str  # "ok" | "degraded"
    version: int
    n_train: int
    refreshes_ok: int
    refreshes_failed: int  # worker exceptions (incl. injected)
    refreshes_rejected: int  # validation-gate refusals
    refreshes_wedged: int  # deadline-abandoned refreshes
    overflow_recoveries: int  # capacity overflows recovered by regrowth
    queries_served: int
    queries_retried: int
    queries_refused: int
    fallback_queries: int  # individual full-miss queries -> prior lane
    staleness: float  # rolling mean miss_mass over the window
    staleness_alert: bool
    last_refresh_s: float | None  # duration of the last completed refresh
    last_failure: str | None
    pending_refresh: bool
    # durability lane (DESIGN.md §14) — defaults keep old constructors valid
    boot_mode: str = "cold"  # "warm" = served from the store at startup
    boot_generation: int | None = None  # store generation served at boot
    boot_skipped: int = 0  # corrupt generations walked past during boot
    persists_ok: int = 0
    persists_failed: int = 0
    persisted_version: int = 0  # newest engine version durable on disk


class PredictorStore:
    """Durable, multi-model Predictor registry on disk (DESIGN.md §14).

    Layout: ``<root>/<model>/gen_<k>/`` — one atomic
    ``gp.serve.save_predictor`` directory per generation, so every
    generation is independently loadable and independently corruptible
    (the warm-boot fallback walks them newest-first). Retention keeps the
    newest ``keep_last`` generations PLUS the single best by the saved
    metric (default: the alpha solve's final CG residual — lower is
    better), so a regression in later generations never deletes the best
    model the store has seen.
    """

    def __init__(self, root: str | pathlib.Path, *, keep_last: int = 3,
                 keep_best: int = 1):
        self.root = pathlib.Path(root)
        self.keep_last = max(keep_last, 1)
        self.keep_best = max(keep_best, 0)
        self._lock = threading.Lock()

    def model_dir(self, name: str) -> pathlib.Path:
        if "/" in name or name in ("", ".", ".."):
            raise ValueError(f"invalid model name {name!r}")
        return self.root / name

    def path(self, name: str, gen: int) -> pathlib.Path:
        return self.model_dir(name) / f"gen_{gen:08d}"

    def models(self) -> list[str]:
        if not self.root.is_dir():
            return []
        return sorted(p.name for p in self.root.iterdir() if p.is_dir())

    def generations(self, name: str) -> list[int]:
        """Generation numbers on disk, ascending (published dirs only —
        a dead ``.tmp`` from a mid-write crash is invisible here)."""
        mdir = self.model_dir(name)
        if not mdir.is_dir():
            return []
        gens = []
        for p in mdir.iterdir():
            if p.is_dir() and p.name.startswith("gen_") \
                    and not p.name.endswith(".tmp"):
                try:
                    gens.append(int(p.name[4:]))
                except ValueError:
                    continue
        return sorted(gens)

    def _metric(self, name: str, gen: int) -> float:
        try:
            man = json.loads(
                (self.path(name, gen) / "manifest.json").read_text())
            return float(man["extra"]["metric"])
        except Exception:
            return float("inf")  # unreadable = never retention-best

    def save(self, name: str, pred: Predictor, *, gen: int | None = None,
             metric: float | None = None, extra: dict | None = None,
             faults: FaultInjector | None = None) -> int:
        """Persist ``pred`` as the next (or given) generation; prune.

        Returns the generation written. ``metric`` feeds keep-best
        retention (lower is better; defaults to the Predictor's CG
        residual). The write itself is ``save_predictor``'s atomic
        tmp+rename; retention runs after publish, so a crash during
        pruning leaves extra generations, never fewer.
        """
        with self._lock:
            if gen is None:
                gens = self.generations(name)
                gen = (gens[-1] + 1) if gens else 1
            if metric is None:
                metric = float(np.asarray(pred.cg_residual))
            save_predictor(pred, self.path(name, gen),
                           extra=dict(extra or {}, metric=metric, gen=gen),
                           faults=faults)
            self._prune(name)
            return gen

    def _prune(self, name: str):
        import shutil
        gens = self.generations(name)
        keep = set(gens[-self.keep_last:])
        if self.keep_best and gens:
            by_metric = sorted(gens, key=lambda g: self._metric(name, g))
            keep.update(by_metric[:self.keep_best])
        for g in gens:
            if g not in keep:
                shutil.rmtree(self.path(name, g), ignore_errors=True)

    def load_newest_valid(self, name: str, *,
                          require_converged: bool = True
                          ) -> tuple[Predictor, int, list[dict]]:
        """Newest generation passing the FULL load gate, falling back
        generation by generation past corrupt/invalid ones.

        Returns ``(pred, gen, skipped)`` where ``skipped`` records every
        newer generation that was rejected (gen + reason) — the warm-boot
        audit trail. Raises ``FileNotFoundError`` when no generation
        loads (the caller cold-freezes instead).
        """
        skipped: list[dict] = []
        for gen in reversed(self.generations(name)):
            try:
                pred = load_predictor(
                    self.path(name, gen),
                    require_converged=require_converged)
                return pred, gen, skipped
            except PredictorLoadError as e:
                skipped.append({"gen": gen, "reason": str(e)})
        err = FileNotFoundError(
            f"{self.model_dir(name)}: no valid predictor generation "
            f"({len(skipped)} rejected)")
        err.skipped = skipped  # cold-boot callers keep the audit trail
        raise err


@dataclasses.dataclass
class _RefreshJob:
    x: Array | None  # None = inputs unchanged (y-only refresh)
    y: Array
    params: GPParams | None  # None = hyperparameters unchanged
    gen: int  # data generation this job carries


class GPServeEngine:
    """Double-buffered Predictor registry + background refresh + health.

    Thread model: ``query`` may be called from any thread; the published
    Predictor is swapped by a single reference assignment under
    ``_lock`` (readers take one reference — pytrees are immutable, so an
    in-flight batch keeps serving its version through a swap; the §10
    replicated-swap contract in sharding/simplex.py covers the mesh
    case). At most one refresh executes at a time; with
    ``background=True`` a worker thread drains the LATEST submitted job
    (intermediate submissions are coalesced — the newest data wins).
    """

    def __init__(self, model: SimplexGP, params: GPParams, x: Array,
                 y: Array, *, key: Array, config: EngineConfig | None = None,
                 faults: FaultInjector | None = None, mesh=None,
                 axis_name: str = "data", background: bool = False,
                 cap: int | None = None, store: PredictorStore | None = None,
                 model_name: str = "default"):
        self.model = model
        self._cfg = config or EngineConfig()
        self._faults = faults
        self._mesh = mesh
        self._axis_name = axis_name
        self._key = key
        self._cap = cap
        self._cache = filtering.LatticeCache()
        self._lock = threading.Lock()
        self._store = store
        self._model_name = model_name
        self._persisted_version = 0
        self._persist_threads: list[threading.Thread] = []
        self._boot = {"mode": "cold", "generation": None, "skipped": 0}
        self._query_ids = itertools.count(1)  # ties a query's spans

        # counters (guarded by _lock)
        self._c = collections.Counter()
        self._last_failure: str | None = None
        self._last_refresh_s: float | None = None
        self._miss_window: collections.deque = collections.deque(
            maxlen=self._cfg.staleness_window)

        # double-buffered registry: version -> Predictor (last 2 kept)
        self._registry: collections.OrderedDict[int, Predictor] = \
            collections.OrderedDict()
        self._version = 0
        self._data_gen = 0  # bumped per submit_refresh
        self._served_gen = 0  # data generation of the published Predictor

        self._watchdog = StepWatchdog(
            window=16, multiplier=self._cfg.refresh_deadline_multiplier,
            min_deadline=self._cfg.refresh_min_deadline_s)

        # boot: prefer the durable store (warm boot — serve the newest
        # generation that passes the full load gate, walking past corrupt
        # ones); cold-freeze from the constructor data only when the
        # store has nothing valid. The engine refuses to START without a
        # valid Predictor either way (no last-good to degrade to yet).
        self._params = params
        self._x, self._y = x, y
        pred = None
        if store is not None:
            try:
                pred, gen, skipped = store.load_newest_valid(
                    model_name,
                    require_converged=self._cfg.require_converged)
                self._boot = {"mode": "warm", "generation": gen,
                              "skipped": len(skipped)}
                if skipped:
                    self._last_failure = (
                        f"boot: skipped {len(skipped)} corrupt "
                        f"generation(s), newest {skipped[0]['gen']}")
            except FileNotFoundError as e:
                pred = None
                self._boot["skipped"] = len(getattr(e, "skipped", ()))
        if pred is None:
            t0 = time.perf_counter()
            pred = freeze(model, params, x, y, key=self._next_key(),
                          variance_rank=self._cfg.variance_rank, cap=cap,
                          cache=self._cache)
            rep = validate_predictor(
                pred, require_converged=self._cfg.require_converged)
            if not rep.ok:
                raise RefreshRejected(
                    "initial freeze failed validation: "
                    + "; ".join(rep.failures))
            dt = time.perf_counter() - t0
            self._watchdog.end_step(dt)
            self._last_refresh_s = dt
        ver = self._publish(pred, gen=0)
        if self._boot["mode"] == "cold":
            # make the boot Predictor durable too (a crash before the
            # first refresh must still warm-boot); warm boot skips this
            # — its generation is already on disk
            self._persist_async(pred, ver)

        # background refresh worker
        self._abandoned: list[threading.Thread] = []
        self._pending: _RefreshJob | None = None
        self._refresh_idle = True
        self._attempted_gen = 0
        self._cond = threading.Condition(self._lock)
        self._stop = threading.Event()
        self._worker: threading.Thread | None = None
        if background:
            self._worker = threading.Thread(
                target=self._worker_loop, name="gp-refresh", daemon=True)
            self._worker.start()

    # -- registry ------------------------------------------------------------

    def _next_key(self) -> Array:
        # locked: an abandoned (wedged) attempt thread may still be
        # splitting keys when the next refresh attempt starts
        with self._lock:
            self._key, sub = jax.random.split(self._key)
            return sub

    def _publish(self, pred: Predictor, *, gen: int) -> int:
        """Atomic hot swap: validate-before-call is the caller's job."""
        if self._mesh is not None:
            from repro.sharding.simplex import replicate_pytree
            pred = replicate_pytree(pred, self._mesh)
        with self._lock:
            self._version += 1
            self._registry[self._version] = pred
            while len(self._registry) > self._cfg.registry_size:
                self._registry.popitem(last=False)
            self._served_gen = max(self._served_gen, gen)
            return self._version

    def _persist_async(self, pred: Predictor, version: int):
        """Persist a just-published Predictor WITHOUT blocking queries.

        Runs on a daemon thread: the query lane never waits on disk, and
        a kill injected at the persistence sites dies off the serving
        path (the published in-memory Predictor already served). Persist
        failures count and degrade health — they never unpublish.
        """
        if self._store is None:
            return

        def work():
            try:
                with obs.span("refresh.persist", version=version):
                    self._store.save(self._model_name, pred,
                                     extra={"engine_version": version},
                                     faults=self._faults)
                with self._lock:
                    self._c["persists_ok"] += 1
                    self._persisted_version = max(self._persisted_version,
                                                  version)
            except Exception as e:  # noqa: BLE001 — degrade, never crash
                with self._lock:
                    self._c["persists_failed"] += 1
                    self._last_failure = f"persist: {e}"

        t = threading.Thread(target=work, name="gp-persist", daemon=True)
        with self._lock:
            self._persist_threads.append(t)
        t.start()

    def wait_persisted(self, version: int | None = None, *,
                       timeout_s: float = 30.0) -> bool:
        """Block until engine ``version`` (default: current) is durable
        on disk, a persist for it has FAILED, or the timeout expires.
        True iff it is durable."""
        if self._store is None:
            return False
        with self._lock:
            want = self._version if version is None else version
            fails0 = self._c["persists_failed"]
        t1 = time.monotonic() + timeout_s
        while time.monotonic() < t1:
            with self._lock:
                if self._persisted_version >= want:
                    return True
                done = not any(t.is_alive() for t in self._persist_threads)
                failed = self._c["persists_failed"] > fails0
            if done and failed:
                return False
            time.sleep(0.005)
        return False

    def predictor(self, version: int | None = None) -> Predictor:
        with self._lock:
            if version is None:
                version = self._version
            return self._registry[version]

    @property
    def version(self) -> int:
        return self._version

    # -- query lane ----------------------------------------------------------

    def query(self, xs: Array, *, timeout_s: float | None = None,
              backend: str | None = None) -> QueryResult:
        """Serve one batch with bounded retry + deadline + fallback lane."""
        qid = next(self._query_ids)
        with obs.span("query", query_id=qid):
            return self._query(xs, qid, timeout_s=timeout_s, backend=backend)

    def _query(self, xs: Array, qid: int, *, timeout_s: float | None,
               backend: str | None) -> QueryResult:
        cfg = self._cfg
        deadline = time.monotonic() + (cfg.query_timeout_s
                                       if timeout_s is None else timeout_s)
        attempts = 0
        while True:
            with obs.span("query.wait", query_id=qid), self._lock:
                version = self._version
                pred = self._registry[version]
                stale = self._served_gen < self._data_gen
            try:
                if self._faults is not None:
                    self._faults.maybe_raise("query")
                with obs.span("query.predict", query_id=qid):
                    sr = predict(pred, xs, backend=backend, mesh=self._mesh,
                                 axis_name=self._axis_name)
                    mean = np.asarray(sr.mean).astype(np.float32)
                    var = np.asarray(sr.var).astype(np.float32)
                    miss = np.asarray(sr.miss_mass)
                with obs.span("query.check", query_id=qid):
                    # prior-fallback lane: a full-miss query's prediction
                    # IS the prior by the slicing math; make the contract
                    # explicit so a fallback response is prior-exact even
                    # if a future table format violates it
                    fb = miss >= cfg.fallback_miss
                    if fb.any():
                        mean[fb] = 0.0
                        var[fb] = float(pred.outputscale)
                    # zero-invalid-responses guarantee: the LAST line of
                    # defense behind the validation gate
                    if not (np.isfinite(mean).all()
                            and np.isfinite(var).all()):
                        raise RuntimeError(
                            "non-finite response from a validated Predictor")
                with self._lock:
                    self._c["queries_served"] += 1
                    self._c["fallback_queries"] += int(fb.sum())
                    if miss.size:  # empty batch would push NaN into the window
                        self._miss_window.append(float(miss.mean()))
                return QueryResult(mean=jnp.asarray(mean),
                                   var=jnp.asarray(var),
                                   miss_mass=sr.miss_mass,
                                   fallback=jnp.asarray(fb),
                                   version=version, stale=stale)
            except Exception as e:
                attempts += 1
                with self._lock:
                    self._c["queries_retried"] += 1
                if attempts > cfg.max_retries or time.monotonic() > deadline:
                    with self._lock:
                        self._c["queries_refused"] += 1
                        self._last_failure = f"query: {e}"
                    raise ServeUnavailable(
                        f"query failed after {attempts} attempt(s)") from e

    # -- refresh lane --------------------------------------------------------

    def submit_refresh(self, *, y: Array, x: Array | None = None,
                       params: GPParams | None = None) -> int:
        """Record new data for the next refresh; returns its generation.

        ``x=None`` means the inputs are unchanged (a y-only refresh —
        the cheap path: cached lattice, reused index, warm-started CG).
        Coalescing: a newer submission replaces an unstarted older one.
        """
        with self._lock:
            self._data_gen += 1
            self._pending = _RefreshJob(x=x, y=y, params=params,
                                        gen=self._data_gen)
            self._cond.notify_all()
            return self._data_gen

    def refresh_now(self, *, wait: bool = True) -> bool:
        """Run the pending refresh inline (sync mode); True on publish.

        With a background worker, prefer ``submit_refresh`` +
        ``wait_refreshed``; this entry point exists for deterministic
        tests and single-threaded deployments.
        """
        with self._lock:
            job, self._pending = self._pending, None
            if job is not None:
                self._refresh_idle = False
        if job is None:
            return False
        return self._run_guarded(job)

    def wait_refreshed(self, gen: int, *, timeout_s: float = 60.0) -> bool:
        """Block until data generation ``gen`` is serving, a refresh for a
        generation >= gen has FAILED (last-good keeps serving), or the
        timeout expires. True iff gen is serving."""
        t1 = time.monotonic() + timeout_s
        while time.monotonic() < t1:
            with self._lock:
                if self._served_gen >= gen:
                    return True
                settled = (self._pending is None
                           and self._refresh_idle
                           and self._attempted_gen >= gen)
            if settled:
                return False
            time.sleep(0.005)
        return False

    def _worker_loop(self):
        while True:
            with self._cond:
                while self._pending is None and not self._stop.is_set():
                    self._cond.wait(timeout=0.1)
                if self._stop.is_set():
                    return
                job, self._pending = self._pending, None
                # mark busy while still holding the lock: wait_refreshed
                # must never observe the gap between dequeue and run
                self._refresh_idle = False
            self._run_guarded(job)

    def _run_guarded(self, job: _RefreshJob) -> bool:
        """One refresh attempt under the wedge deadline; never raises."""
        with obs.span("refresh", gen=job.gen) as attrs:
            attrs["published"] = ok = self._attempt(job)
        return ok

    def _attempt(self, job: _RefreshJob) -> bool:
        self._refresh_idle = False
        result: dict = {}

        def work():
            try:
                result["pred"] = self._do_refresh(job)
            except BaseException as e:  # noqa: BLE001 — the guard reports
                result["err"] = e

        deadline = self._watchdog.deadline
        if self._cfg.refresh_max_deadline_s is not None:
            deadline = min(deadline, self._cfg.refresh_max_deadline_s)
        t0 = time.perf_counter()
        t = threading.Thread(target=work, name="gp-refresh-attempt",
                             daemon=True)
        t.start()
        t.join(None if deadline == float("inf") else deadline)
        try:
            if t.is_alive():
                # wedged: abandon — the attempt thread's result dict is
                # never read again, so a late finish can never publish
                with self._lock:
                    self._c["refreshes_wedged"] += 1
                    self._abandoned.append(t)
                    self._last_failure = (
                        f"refresh wedged (> {deadline:.2f}s deadline), "
                        "last-good predictor kept")
                return False
            dt = time.perf_counter() - t0
            if "err" in result:
                with self._lock:
                    if isinstance(result["err"], RefreshRejected):
                        self._c["refreshes_rejected"] += 1
                    self._c["refreshes_failed"] += 1
                    self._last_failure = f"refresh: {result['err']}"
                return False
            self._watchdog.end_step(dt)
            ver = self._publish(result["pred"], gen=job.gen)
            self._persist_async(result["pred"], ver)
            with self._lock:
                # accepted: advance the engine's notion of train data HERE
                # (not in _do_refresh) so an abandoned wedged attempt that
                # finishes late can never mutate engine state
                if job.x is not None:
                    self._x = job.x
                self._y = job.y
                if job.params is not None:
                    self._params = job.params
                self._c["refreshes_ok"] += 1
                self._last_refresh_s = dt
            return True
        finally:
            with self._lock:
                self._attempted_gen = max(self._attempted_gen, job.gen)
                self._refresh_idle = True

    def _do_refresh(self, job: _RefreshJob) -> Predictor:
        """Build + validate one candidate (runs on the attempt thread)."""
        cfg = self._cfg
        faults = self._faults
        if faults is not None:
            faults.maybe_raise("refresh")
            faults.sleep_if_armed("freeze")

        x = self._x if job.x is None else job.x
        params = self._params if job.params is None else job.params
        model = self.model
        if faults is not None and faults.cg_stall("freeze"):
            # force a genuinely non-converged solve (not a faked flag):
            # a tolerance no f32 solve reaches in 2 iterations
            model = SimplexGP(dataclasses.replace(
                model.config, cg_tol_eval=1e-12, max_cg_iters=2))

        cap = self._cap
        if faults is not None:
            forced = faults.forced_cap("freeze")
            if forced is not None:
                cap = forced
        old = self.predictor()
        cand = None
        for attempt in range(cfg.max_cap_retries + 1):
            try:
                with obs.span("refresh.freeze", gen=job.gen, cap=cap):
                    cand = refreeze(model, params, x, job.y,
                                    key=self._next_key(), old=old,
                                    cache=self._cache, cap=cap,
                                    variance_rank=cfg.variance_rank)
                break
            except RuntimeError as e:
                if ("capacity overflow" not in str(e)
                        or attempt == cfg.max_cap_retries):
                    raise
                # grown-cap recovery; final retry escalates to the
                # worst-case auto sizing, which cannot capacity-overflow
                cap = (None if cap is None or attempt >= 1
                       else cap * cfg.cap_growth)
                with self._lock:
                    self._c["overflow_recoveries"] += 1

        if faults is not None:
            cand = dataclasses.replace(
                cand, tables=faults.corrupt_tables("freeze", cand.tables))

        with obs.span("refresh.validate", gen=job.gen):
            rep = validate_predictor(cand,
                                     require_converged=cfg.require_converged)
        if not rep.ok:
            raise RefreshRejected("candidate refused: "
                                  + "; ".join(rep.failures))
        return cand

    # -- health lane ---------------------------------------------------------

    @property
    def staleness(self) -> float:
        with self._lock:
            if not self._miss_window:
                return 0.0
            return float(sum(self._miss_window) / len(self._miss_window))

    def health(self) -> HealthStatus:
        stal = self.staleness
        with self._lock:
            c = self._c
            degraded = (self._served_gen < self._data_gen
                        and self._pending is None and self._refresh_idle)
            ok = not degraded and not (
                stal > self._cfg.staleness_alert)
            return HealthStatus(
                status="ok" if ok else "degraded",
                version=self._version,
                n_train=self._registry[self._version].n_train,
                refreshes_ok=c["refreshes_ok"],
                refreshes_failed=c["refreshes_failed"],
                refreshes_rejected=c["refreshes_rejected"],
                refreshes_wedged=c["refreshes_wedged"],
                overflow_recoveries=c["overflow_recoveries"],
                queries_served=c["queries_served"],
                queries_retried=c["queries_retried"],
                queries_refused=c["queries_refused"],
                fallback_queries=c["fallback_queries"],
                staleness=stal,
                staleness_alert=stal > self._cfg.staleness_alert,
                last_refresh_s=self._last_refresh_s,
                last_failure=self._last_failure,
                pending_refresh=self._pending is not None
                or not self._refresh_idle,
                boot_mode=self._boot["mode"],
                boot_generation=self._boot["generation"],
                boot_skipped=self._boot["skipped"],
                persists_ok=c["persists_ok"],
                persists_failed=c["persists_failed"],
                persisted_version=self._persisted_version,
            )

    # -- lifecycle -----------------------------------------------------------

    def close(self, *, timeout_s: float = 30.0):
        self._stop.set()
        with self._cond:
            self._cond.notify_all()
        if self._worker is not None:
            self._worker.join(timeout_s)
        # abandoned (wedged) attempt threads may still be inside device
        # work; give them a bounded chance to drain so interpreter
        # teardown never kills a thread mid-XLA-call
        with self._lock:
            abandoned = list(self._abandoned)
            persisting = list(self._persist_threads)
        for t in abandoned:
            t.join(timeout_s)
        for t in persisting:  # drain in-flight persists (bounded)
            t.join(timeout_s)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

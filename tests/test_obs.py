"""repro.obs: layer scopes in the compiled programs, host spans, counters
and the JIT record (DESIGN.md §17).

The scope vocabulary is a contract with whoever reads a device trace: each
name must reach the ``op_name`` metadata of the programs the benchmark
runs — the training step and the validation posterior — or of the serving
slice. Checked on the CPU at a small protein-shaped size (d = 9).
"""
import glob
import re
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.gp import (GPParams, SimplexGP, SimplexGPConfig, fit, freeze,
                      mll_value_and_grad, posterior)
from repro.gp.serve import predict
from repro.launch.serve_gp import EngineConfig, GPServeEngine

N, NV, D = 160, 40, 9
CFG = dict(kernel="matern32", num_probes=4, max_cg_iters=12,
           max_lanczos_iters=6)


def _data(seed=0, n=N, nv=NV):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.uniform(-1, 1, size=(n + nv, D)), jnp.float32)
    y = jnp.sin(2 * x[:, 0]) + 0.3 * x[:, 1] * x[:, 2]
    return x[:n], y[:n], x[n:], y[n:nv + n]


def scopes_in(compiled_text: str) -> set[str]:
    """The ``simplex.`` path components of every op_name in a compiled
    program's metadata."""
    out = set()
    for name in re.findall(r'op_name="([^"]*)"', compiled_text):
        out.update(c for c in name.split("/") if c.startswith(obs.PREFIX))
    return out


def innermost(op_name: str) -> str | None:
    """The layer a device op belongs to."""
    comps = [c for c in op_name.split("/") if c.startswith(obs.PREFIX)]
    return comps[-1] if comps else None


@pytest.fixture(scope="module")
def compiled():
    """Compiled text of the step (default estimator; and the SLQ estimator
    with a pivoted-Cholesky preconditioner), of the validation posterior
    and of the serving slice."""
    x, y, xv, _ = _data()
    key = jax.random.PRNGKey(0)
    params = GPParams.init(D)
    out = {}
    for name, extra in (("step", {}),
                        ("step_precond", dict(precond_rank=4,
                                              logdet_estimator="slq"))):
        model = SimplexGP(SimplexGPConfig(**CFG, **extra))
        out[name] = jax.jit(lambda p, k, m=model: mll_value_and_grad(
            m, p, x, y, k, cap=2048)).lower(params, key).compile().as_text()
    model = SimplexGP(SimplexGPConfig(**CFG))
    out["posterior"] = jax.jit(lambda p, k: posterior(
        model, p, x, y, xv, key=k, variance_rank=5, cap=4096)).lower(
        params, key).compile().as_text()
    pred = freeze(model, params, x, y, key=key, variance_rank=5)
    out["serve"] = jax.jit(lambda q: predict(pred, q)).lower(
        xv).compile().as_text()
    return out


@pytest.mark.parametrize("name", [s for s in obs.SCOPES
                                  if s != "simplex.mvm.psum"])
def test_every_scope_reaches_the_compiled_metadata(compiled, name):
    where = [k for k, text in compiled.items() if name in scopes_in(text)]
    assert where, f"{name} is in no compiled program"


def test_step_and_posterior_name_their_layers(compiled):
    build = {"simplex.build", "simplex.build.embed", "simplex.build.dedup",
             "simplex.build.neighbours", "simplex.build.splat_plan"}
    mvm = {"simplex.mvm", "simplex.mvm.splat", "simplex.mvm.blur",
           "simplex.mvm.slice"}
    assert build | mvm | {"simplex.cg", "simplex.slq",
                          "simplex.grad"} <= scopes_in(compiled["step"])
    assert build | mvm | {"simplex.cg", "simplex.lanczos",
                          "simplex.posterior.cross",
                          "simplex.posterior.var"} <= scopes_in(
        compiled["posterior"])
    assert {"simplex.precond", "simplex.lanczos",
            "simplex.slq"} <= scopes_in(compiled["step_precond"])
    assert "simplex.serve.slice" in scopes_in(compiled["serve"])
    # a CG iteration's MVM nests inside the solver's scope
    assert re.search(r'op_name="[^"]*simplex\.cg/[^"]*simplex\.mvm\.blur/',
                     compiled["step"])


def test_innermost_scope_is_the_layer():
    assert innermost("jit(step)/simplex.cg/while/body/simplex.mvm/"
                     "jit(_fused_xla)/simplex.mvm.blur/gather") \
        == "simplex.mvm.blur"
    assert innermost("jit(step)/add") is None


@pytest.mark.parametrize("backend", ["sort", "hash_xla"])
def test_both_build_paths_name_their_phases(backend):
    from repro.core.lattice import build_lattice
    z, *_ = _data()
    text = jax.jit(lambda zz: build_lattice(
        zz, spacing=1.0, r=1, cap=2048, backend=backend)).lower(
        z).compile().as_text()
    assert {"simplex.build", "simplex.build.embed", "simplex.build.dedup",
            "simplex.build.neighbours",
            "simplex.build.splat_plan"} <= scopes_in(text)


@pytest.mark.multidevice
def test_sharded_mvm_names_its_psum(multidevice_run):
    code = (
        "import json, re, jax, jax.numpy as jnp, numpy as np\n"
        "from repro.core.lattice import build_lattice\n"
        "from repro.core.stencil import make_stencil\n"
        "from repro.kernels.blur.ops import lattice_mvm\n"
        "from repro.sharding.simplex import data_mesh\n"
        "rng = np.random.default_rng(0)\n"
        "z = jnp.asarray(rng.normal(size=(64, 3)), jnp.float32)\n"
        "v = jnp.asarray(rng.normal(size=(64, 2)), jnp.float32)\n"
        "st = make_stencil('matern32', 1)\n"
        "lat = build_lattice(z, spacing=st.spacing, r=st.r)\n"
        "mesh = data_mesh(8)\n"
        "text = jax.jit(lambda vv: lattice_mvm(lat, vv, taps=st.weights,"
        " mesh=mesh)).lower(v).compile().as_text()\n"
        "psum = [n for n in re.findall(r'op_name=\"([^\"]*)\"', text)"
        " if 'simplex.mvm.psum' in n]\n"
        "ar = [l for l in text.splitlines() if 'all-reduce(' in l"
        " and 'simplex.mvm.psum' in l]\n"
        "print(json.dumps({'psum': len(psum), 'all_reduce': len(ar)}))\n")
    got = multidevice_run(code)
    assert got["psum"] > 0 and got["all_reduce"] >= 1


def test_unknown_names_are_refused():
    for fn in (obs.scope, obs.count, obs.counter):
        with pytest.raises(ValueError):
            fn("no.such.name")
    with pytest.raises(ValueError):
        with obs.span("no.such.span"):
            pass


# -- host spans ----------------------------------------------------------------


def test_record_keeps_nested_spans_with_parents():
    with obs.record() as spans:
        with obs.span("epoch", epoch=0):
            with obs.span("step"):
                pass
            with obs.span("validation") as attrs:
                attrs["late"] = 1
    by = {s.name: s for s in spans}
    assert [s.name for s in spans] == ["step", "validation", "epoch"]
    assert by["epoch"].parent_id is None
    assert by["step"].parent_id == by["epoch"].id
    assert by["validation"].parent_id == by["epoch"].id
    assert by["validation"].attrs == {"late": 1}
    assert by["epoch"].attrs == {"epoch": 0}
    assert by["epoch"].t0 <= by["step"].t0 <= by["step"].t1 \
        <= by["validation"].t0 <= by["validation"].t1 <= by["epoch"].t1


def test_spans_off_keep_nothing():
    with obs.record() as spans:
        pass
    with obs.span("epoch"):
        with obs.span("step"):
            pass
    assert spans == []


def test_threads_keep_their_own_parents():
    with obs.record() as spans:
        with obs.span("refresh", gen=1):
            done = []

            def work():
                with obs.span("refresh.freeze", gen=1):
                    done.append(1)

            t = threading.Thread(target=work)
            t.start()
            t.join(30)
    assert done == [1] and not t.is_alive()
    by = {s.name: s for s in spans}
    # another thread's span has no parent on this thread's stack
    assert by["refresh.freeze"].parent_id is None
    assert by["refresh.freeze"].attrs == {"gen": 1}


def test_fit_records_epoch_step_and_validation_spans():
    x, y, xv, yv = _data(1)
    model = SimplexGP(SimplexGPConfig(**CFG))
    with obs.record() as spans:
        res = fit(model, x, y, x_val=xv, y_val=yv, epochs=2, lr=0.1,
                  patience=5)
    assert len(res.history) == 2
    names = [s.name for s in spans]
    for want in ("build_auto", "epoch", "step", "step.read", "validation"):
        assert want in names, (want, names)
    epochs = [s for s in spans if s.name == "epoch"]
    assert [s.attrs["epoch"] for s in epochs] == [0, 1]
    ids = {s.id for s in epochs}
    for s in spans:
        if s.name in ("step", "step.read", "validation"):
            assert s.parent_id in ids
    assert all(s.attrs["tries"] >= 1 for s in spans
               if s.name == "build_auto")
    # both CG counts ride on the spans and in the history
    for name, key in (("step.read", "cg_iters"),
                      ("validation", "val_cg_iters")):
        got = [s.attrs["cg_iters"] for s in spans if s.name == name]
        assert got == [h[key] for h in res.history], (name, got)
        assert all(0 < i <= CFG["max_cg_iters"] for i in got), got


def test_engine_query_spans_share_a_query_id():
    x, y, xv, _ = _data(2)
    model = SimplexGP(SimplexGPConfig(**dict(CFG, max_cg_iters=200)))
    eng = GPServeEngine(model, GPParams.init(D, noise=0.3), x, y,
                        key=jax.random.PRNGKey(0),
                        config=EngineConfig(variance_rank=5))
    try:
        with obs.record() as spans:
            eng.query(xv)
            eng.query(xv[:7])
    finally:
        eng.close()
    queries = [s for s in spans if s.name == "query"]
    assert len(queries) == 2
    for q in queries:
        parts = [s for s in spans if s.parent_id == q.id]
        assert {s.name for s in parts} == {"query.wait", "query.predict",
                                           "query.check"}
        assert all(s.attrs["query_id"] == q.attrs["query_id"] for s in parts)
    assert queries[0].attrs["query_id"] != queries[1].attrs["query_id"]


# -- counters and the JIT record -----------------------------------------------


def test_counters_count_at_trace_level():
    from repro.core.lattice import build_lattice
    z = _data()[0][:77]  # a shape no other test traces
    b0 = obs.counter("lattice.builds")
    e0 = obs.counter("lattice.embeds")
    fn = jax.jit(lambda zz: build_lattice(zz, spacing=1.0, r=1,
                                          cap=2048).m)
    fn(z)
    fn(z)  # cached: no second trace, no second count
    assert obs.counter("lattice.builds") - b0 == 1
    assert obs.counter("lattice.embeds") - e0 == 1


def test_a_retrace_lands_in_the_jit_record():
    def obs_probe_fn(v):
        return jnp.cumsum(v) * 2.0

    fn = jax.jit(obs_probe_fn)
    fn(jnp.ones((3,)))
    n0 = len([e for e in obs.jit_events() if "obs_probe_fn" in e.fun_name])
    fn(jnp.ones((5,)))  # a new shape: traced, lowered and compiled again
    got = [e for e in obs.jit_events() if "obs_probe_fn" in e.fun_name]
    kinds = {e.kind for e in got[n0:]}
    assert kinds == {"trace", "lower", "compile"}, got
    assert all(e.t0 <= e.t1 for e in got)


def test_profiler_trace_holds_simplex_host_spans(tmp_path):
    from jax.profiler import ProfileData
    fn = jax.jit(lambda v: jnp.sin(v) * 2)
    fn(jnp.ones((8,)))
    with jax.profiler.trace(str(tmp_path)):
        with obs.span("step"):
            jax.block_until_ready(fn(jnp.ones((8,))))
    path = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)[0]
    host = [ev.name for p in ProfileData.from_file(path).planes
            if p.name.startswith("/host:") for line in p.lines
            for ev in line.events]
    assert "simplex.step" in host

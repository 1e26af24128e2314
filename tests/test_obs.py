"""repro.obs: spans, counters and compile time, and the stage-2 program's
spans and named scopes (core/dense.py, core/ensemble.py)."""
import dataclasses
import glob
import re
import time
import warnings

import jax
import jax.numpy as jnp
import pytest

from repro import obs
from repro.configs.paper_cifar import DenseExperimentConfig
from repro.core import Client, train_dense_server
from repro.models.cnn import CNNSpec, cnn_init

SCFG = DenseExperimentConfig(
    n_clients=3, num_classes=4, image_size=8, in_ch=1, width=0.25,
    client_kinds=("cnn1", "cnn1", "resnet18"), global_kind="cnn1", nz=8,
    t_g=2, epochs=4, synth_batch=8, loop_chunk=2)


def _clients(kinds=SCFG.client_kinds):
    specs = [CNNSpec(kind=k, num_classes=4, in_ch=1, width=0.25,
                     image_size=8) for k in kinds]
    return [Client(spec=s, params=cnn_init(jax.random.PRNGKey(i), s))
            for i, s in enumerate(specs)]


@pytest.fixture(autouse=True)
def fresh():
    obs.reset()
    yield
    obs.reset()


def _spans(name=None):
    return [s for s in obs.snapshot()["spans"]
            if name is None or s["name"] == name]


def test_spans_nest_and_name_their_parent():
    with obs.span("outer", lo=0, hi=8):
        with obs.span("inner"):
            pass
        with obs.span("inner"):
            pass
    with obs.span("after"):
        pass
    recs = _spans()
    assert [s["name"] for s in recs] == ["inner", "inner", "outer", "after"]
    assert [s["parent"] for s in recs] == ["outer", "outer", None, None]
    outer = recs[2]
    assert outer["attrs"] == {"lo": 0, "hi": 8}
    for s in recs[:2]:
        assert outer["start_ns"] <= s["start_ns"] <= s["end_ns"] \
            <= outer["end_ns"]


def test_span_records_on_error():
    with pytest.raises(KeyError):
        with obs.span("failing"):
            raise KeyError("x")
    assert [s["name"] for s in _spans()] == ["failing"]
    with obs.span("next"):
        pass
    assert _spans("next")[0]["parent"] is None


def test_ring_is_bounded():
    for i in range(obs.RING_SIZE + 10):
        with obs.span("s", i=i):
            pass
    recs = _spans()
    assert len(recs) == obs.RING_SIZE
    assert recs[0]["attrs"]["i"] == 10
    assert recs[-1]["attrs"]["i"] == obs.RING_SIZE + 9


def test_counters_add_up():
    obs.count("a")
    obs.count("a", 3)
    obs.count("b", 2)
    assert obs.snapshot()["counters"] == {"a": 4, "b": 2}
    obs.reset()
    assert obs.snapshot()["counters"] == {}


@pytest.fixture
def persistent_cache(tmp_path):
    """A persistent compilation cache in ``tmp_path`` that keeps every
    entry, restored afterwards."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    was = {n: getattr(jax.config, n) for n in names}
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    cc.reset_cache()
    yield
    for n, v in was.items():
        jax.config.update(n, v)
    cc.reset_cache()


def test_compile_is_charged_to_the_innermost_span(persistent_cache):
    def f(x):
        return jnp.tanh(x) @ x.T + 3.0

    x = jnp.ones((8, 8))
    x.block_until_ready()
    with obs.span("outer"):
        with obs.span("first"):
            jax.jit(f)(x).block_until_ready()
    jax.clear_caches()
    with obs.span("again"):
        jax.jit(f)(x).block_until_ready()
    jax.jit(lambda y: y * 5.0)(x).block_until_ready()
    snap = obs.snapshot()
    first, again = snap["compile"]["first"], snap["compile"]["again"]
    assert "outer" not in snap["compile"]
    assert first["compiles"] == 1 and first["cache_misses"] == 1
    assert first["trace_s"] > 0 and first["lower_s"] > 0
    assert first["backend_s"] > 0
    assert again["compiles"] == 1 and again["cache_hits"] == 1
    assert 0 < again["cache_load_s"] <= again["backend_s"]
    assert snap["compile"][obs.NO_SPAN]["compiles"] >= 1
    for e in (first, again):
        assert e["seconds"] == pytest.approx(
            e["trace_s"] + e["lower_s"] + e["backend_s"])
    assert snap["compile_s"] == pytest.approx(
        sum(e["seconds"] for e in snap["compile"].values()))
    # the spans opened after the first compile saw it on the clock
    assert _spans("again")[0]["compile_s_at_start"] >= first["seconds"]


def test_nested_traces_are_charged_once():
    @jax.jit
    def inner(x):
        return jnp.sin(x) * 2.0

    @jax.jit
    def outer(x):
        return inner(x) + inner(x + 1.0)

    x = jnp.ones(5)
    x.block_until_ready()
    t0 = time.perf_counter()
    with obs.span("nested"):
        outer(x).block_until_ready()
    wall = time.perf_counter() - t0
    got = obs.snapshot()["compile"]["nested"]
    assert got["compiles"] == 1
    assert 0 < got["seconds"] <= wall


def _trained(scfg, **kw):
    return train_dense_server(jax.random.PRNGKey(0), _clients(), scfg, **kw)


def test_fused_chunk_spans_show_in_a_profile(tmp_path):
    from jax.profiler import ProfileData
    scfg = dataclasses.replace(SCFG, loop_mode="fused")
    _trained(scfg)                     # compile outside the trace
    obs.reset()
    jax.profiler.start_trace(str(tmp_path))
    try:
        _trained(scfg, eval_fn=lambda p, s: 0.0, eval_every=2)
    finally:
        jax.profiler.stop_trace()
    snap = obs.snapshot()
    # the stacked cnn1 pair at synth batch 8 is traced once in each of
    # the two steps, as im2col GEMMs
    assert snap["counters"] == {"dense.chunks": 2, "dense.epochs": 4,
                                "dense.host_syncs": 2,
                                "ensemble.grouped_im2col": 2}
    chunks = _spans("dense.chunk")
    assert [c["attrs"] for c in chunks] == [{"lo": 0, "hi": 2},
                                            {"lo": 2, "hi": 4}]
    for name in ("dense.dispatch", "dense.sync", "dense.history",
                 "dense.eval"):
        assert [s["parent"] for s in _spans(name)] == ["dense.chunk"] * 2
    assert [s["parent"] for s in _spans("dense.init")] == ["dense.setup"]

    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    host = {}
    for plane in ProfileData.from_file(path[0]).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("dense."):
                        host.setdefault(e.name, []).append(
                            (e.duration_ns, dict(e.stats)))
    for name in ("dense.chunk", "dense.dispatch", "dense.sync"):
        mem = [s["end_ns"] - s["start_ns"] for s in _spans(name)]
        prof = [d for d, _ in host[name]]
        assert len(prof) == len(mem) == 2
        for a, b in zip(sorted(prof), sorted(mem)):
            assert abs(a - b) < 1e6
    stats = sorted((int(s["lo"]), int(s["hi"])) for _, s in
                   host["dense.chunk"])
    assert stats == [(0, 2), (2, 4)]


def test_python_driver_spans_and_rollbacks():
    scfg = dataclasses.replace(SCFG, loop_mode="python", epochs=3,
                               nan_policy="rollback")
    _trained(scfg, _poison_epochs=[1])
    snap = obs.snapshot()
    assert [s["attrs"] for s in _spans("dense.epoch")] == \
        [{"epoch": e} for e in range(3)]
    assert snap["counters"]["dense.epochs"] == 3
    assert snap["counters"]["dense.rollbacks"] == 1
    # both losses and the three parts are fetched each epoch
    assert snap["counters"]["dense.host_syncs"] == 3 * 5
    assert "dense.chunks" not in snap["counters"]


def _scope(op_name):
    for part in op_name.split(";")[0].split("/"):
        if part.startswith("jit("):
            continue
        core = re.sub(r"^(\w+\()+|\)+$", "", part)
        if core in obs.SCOPES:
            return core
    return None


def test_every_conv_and_dot_of_the_chunk_carries_a_scope():
    # the fused driver keeps its chunk program; its compiled HLO is what
    # a trace reduction maps device ops to scopes through
    assert obs.program_text("dense.epochs_step") is None
    _trained(dataclasses.replace(SCFG, loop_mode="fused"))
    hlo = obs.program_text("dense.epochs_step")
    assert obs.program_text("dense.epochs_step") is hlo
    ops = re.findall(r"^\s*(?:ROOT )?%\S+ = .*? (convolution|dot)\((.*)$",
                     hlo, re.M)
    assert len(ops) > 50
    seen, groups = set(), set()
    for _, rest in ops:
        name = re.search(r'op_name="([^"]*)"', rest)
        assert name, rest[:200]
        scope = _scope(name.group(1))
        assert scope in obs.SCOPES, name.group(1)
        seen.add(scope)
        if scope == obs.TEACHER:
            groups.add(re.search(r"teacher\)*/(\w+)", name.group(1))[1])
    assert seen == {obs.TEACHER, obs.STUDENT, obs.GENERATOR}
    assert groups == {"cnn1", "resnet18"}


def test_teacher_counts_the_clients_a_mesh_shards():
    """Tracing the teacher on a client mesh that divides its stacked
    group counts the group's clients as sharded; a singleton group is
    neither, and without a mesh nothing is counted."""
    from repro.core.ensemble import grouped_ensemble_logits, stack_grouped
    from repro.fl import sharding as FS
    from repro.launch.mesh import make_client_mesh
    clients = _clients(("cnn1",) * 8 + ("resnet18",))
    gspecs, gparams = stack_grouped(clients)
    x = jnp.zeros((4, 8, 8, 1))
    grouped_ensemble_logits(gspecs, gparams, x)
    assert not any(k.endswith("_clients")
                   for k in obs.snapshot()["counters"])
    mesh = make_client_mesh()
    placed = FS.put_grouped(gspecs, gparams, mesh)
    jax.jit(lambda gp, xb: grouped_ensemble_logits(
        gspecs, gp, xb, with_bn_stats=True, mesh=mesh)).lower(placed, x)
    counters = obs.snapshot()["counters"]
    assert counters["ensemble.sharded_clients"] == 8
    assert "ensemble.replicated_clients" not in counters


def test_a_group_the_mesh_cannot_shard_is_counted_and_warned_once():
    """A stacked group that the clients axis does not divide runs
    replicated: its clients are counted as such, and the first fallback
    of each group shape warns."""
    from types import SimpleNamespace

    from repro.fl import sharding as FS
    mesh = SimpleNamespace(shape={"clients": 7, "data": 1})
    assert FS.shards_group(mesh, 14, "cnn1")
    with pytest.warns(RuntimeWarning, match="3-client cnn1 group"):
        assert not FS.shards_group(mesh, 3, "cnn1")
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        assert not FS.shards_group(mesh, 3, "cnn1")
        assert not FS.shards_group(mesh, 1, "cnn1")
        assert not FS.shards_group(None, 3, "cnn1")
    counters = obs.snapshot()["counters"]
    assert counters["ensemble.sharded_clients"] == 14
    assert counters["ensemble.replicated_clients"] == 6

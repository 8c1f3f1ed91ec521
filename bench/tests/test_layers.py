"""The layer reduction (bench/layers.py) and the readers of its metrics:
op names from a compiled step's HLO, scopes from op names, device time
put down to the innermost scoped op, idle gaps to the loop's spans; on
made-up events and on one recorded m3 train step of the chip."""
import gzip
import importlib.util
import json

import jax
import jax.numpy as jnp
import pytest

from bench import layers, trace
from bench.tests.conftest import ROOT
from repro.tracing import SCOPES

DEV = "/device:TPU:0"


def reader(name):
    path = ROOT / "bench/metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(name.replace(".", "_"),
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def op(name, ts, dur, line=trace.OPS_LINE, plane=DEV):
    return {"plane": plane, "line": line, "name": name, "ts": ts,
            "dur": dur}


def host(name, ts, dur):
    return {"plane": "/host:CPU", "line": "python3", "name": name,
            "ts": ts, "dur": dur}


@pytest.mark.parametrize("op_name,want", [
    ("jit(step)/jvp(bottom_mlp)/dot_general", ("bottom_mlp", False)),
    ("jit(step)/transpose(jvp(top_mlp))/...i,io->...o/dot_general",
     ("top_mlp", True)),
    ("jit(step)/embedding_pool/while/body/closed_call/embedding_remap/"
     "jit(searchsorted)/while/body/lt", ("embedding_remap", False)),
    ("jit(step)/embedding_pool/while/body/dynamic_slice",
     ("embedding_pool", False)),
    ("jit(loss)/mul", (None, False)),          # a function, not a scope
    ("reduce_sum", (None, False)),
])
def test_scope_of(op_name, want):
    assert layers.scope_of(op_name, SCOPES) == want


def test_hlo_op_names_of_a_compiled_function():
    def f(w, x):
        with jax.named_scope("bottom_mlp"):
            h = jnp.tanh(x @ w)
        with jax.named_scope("loss"):
            return jnp.sum(h * h)

    text = jax.jit(jax.value_and_grad(f)).lower(
        jnp.ones((8, 8)), jnp.ones((4, 8))).compile().as_text()
    module, names = layers.hlo_op_names(text)
    assert module == "jit_f"
    found = {layers.scope_of(v, SCOPES) for v in names.values()}
    assert {("bottom_mlp", False), ("bottom_mlp", True),
            ("loss", False)} <= found
    # every instruction of the entry computation that carries metadata
    root = [ln for ln in text.splitlines() if ln.lstrip().startswith("ROOT")
            and "op_name=" in ln]
    assert root and all(layers.instruction(ln.split("ROOT", 1)[1].strip())
                        in names for ln in root)
    with pytest.raises(ValueError):
        layers.hlo_op_names("not hlo")


NAMES = {"while.1": "jit(step)/embedding_pool/while",
         "fusion.2": "jit(step)/embedding_pool/while/body/embedding_remap/lt",
         "fusion.3": "jit(step)/embedding_pool/while/body/add",
         "fusion.4": "jit(step)/transpose(jvp(top_mlp))/dot_general",
         "fusion.5": "jit(step)/jvp(bottom_mlp)/dot_general",
         "copy.6": "jit(step)/copy"}


def made_up():
    """One step-module run [0, 200) and an unrelated module's [300, 310)
    whose instruction names collide with the step's."""
    return [
        op("jit_step(123)", 0, 200, line=layers.MODULES_LINE),
        op("jit_other(9)", 300, 10, line=layers.MODULES_LINE),
        op("%while.1 = (s32[]) while()", 10, 50),
        op("%fusion.2 = f32[8] fusion()", 20, 10),      # nested in the loop
        op("%fusion.3 = f32[8] fusion()", 35, 10),      # unscoped body op
        op("%fusion.4 = f32[8] fusion()", 70, 20),
        op("%fusion.5 = f32[8] fusion()", 85, 15),      # overlaps fusion.4
        op("%copy.6 = f32[8] copy()", 120, 5),          # scope-less metadata
        op("%custom.7 = f32[8] custom-call()", 130, 5),  # not in the map
        op("%fusion.2 = f32[8] fusion()", 302, 4),      # other module
        op("%fusion.4 = f32[8] fusion()", 400, 6),      # no module at all
    ]


def test_nested_ops_count_once_and_outside_ops_are_unscoped():
    r = layers.reduce(made_up(), "jit_step", NAMES, SCOPES)
    assert r["steps"] == 1
    assert r["layer_s"] == pytest.approx(
        {"embedding_pool": 40e-9, "embedding_remap": 10e-9,
         "top_mlp": 15e-9, "bottom_mlp": 15e-9})     # the later op holds
    assert r["backward_s"] == pytest.approx({"top_mlp": 15e-9})
    assert r["unscoped_s"] == pytest.approx(20e-9)
    busy = trace.reduce(made_up())["busy_s"]
    assert r["busy_s"] == pytest.approx(busy)
    assert sum(r["layer_s"].values()) + r["unscoped_s"] == pytest.approx(busy)
    assert r["device_ops"] == [
        ["embedding_pool/while", pytest.approx(50e-9)],
        ["top_mlp/fusion", pytest.approx(20e-9)],
        ["bottom_mlp/fusion", pytest.approx(15e-9)],
        ["fusion", pytest.approx(10e-9)],
        ["copy", pytest.approx(5e-9)], ["custom", pytest.approx(5e-9)]]


def test_an_idle_gap_goes_to_the_span_that_overlaps_it_most():
    events = [op("jit_step(1)", 0, 100, line=layers.MODULES_LINE),
              op("%fusion.5 = f32[8] fusion()", 0, 10),
              op("%fusion.5 = f32[8] fusion()", 50, 10),
              op("%fusion.5 = f32[8] fusion()", 62, 10),
              host("outer", 0, 100),
              host("train.loss_read", 0, 15),        # 5 of the gap
              host("train.next_batch", 15, 5),       # 5
              host("train.dispatch", 20, 28)]        # 28: the most
    r = layers.reduce(events, "jit_step", NAMES, SCOPES)
    assert r["idle_gaps"] == [["train.dispatch", pytest.approx(40e-9)],
                              ["outer", pytest.approx(2e-9)]]
    assert r["gap_s"] == pytest.approx({"train.dispatch": 40e-9,
                                        "no train span": 2e-9})


def test_readers_of_the_layer_metrics():
    r = layers.reduce(made_up(), "jit_step", NAMES, SCOPES)
    rec = {"layers": r, "window_s": 2.0,
           "loop": {"phase_s": {"next_batch": 0.01}, "compiles": [7]}}
    assert reader("mlp_ms.train")(rec) == pytest.approx(30e-6)
    assert reader("embedding_remap_ms.train")(rec) == pytest.approx(10e-6)
    assert reader("sparse_plan_ms.train")(rec) is None      # never ran
    assert reader("unscoped_share.train")(rec) == pytest.approx(
        100 * 20 / 100)
    assert reader("loop_wait_share.train")(rec) == pytest.approx(0.5)
    assert reader("window_compiles.train")(rec) == 1
    for name in ("sparse_plan_ms.train", "embedding_remap_ms.train",
                 "mlp_ms.train", "bag_grad_sums_ms.train",
                 "unscoped_share.train", "loop_wait_share.train",
                 "window_compiles.train"):
        assert reader(name)({"window_s": 1.0}) is None, name


@pytest.fixture(scope="module")
def recorded():
    path = ROOT / "bench/tests/data/layers_m3_one_step.json.gz"
    with gzip.open(path, "rt") as f:
        return json.load(f)


def test_recorded_m3_step_layers_and_unscoped_make_busy(recorded):
    r = layers.reduce(recorded["events"], recorded["module"],
                      recorded["op_names"], SCOPES)
    busy = trace.reduce(recorded["events"])["busy_s"]
    assert r["steps"] == 1
    total = sum(r["layer_s"].values()) + r["unscoped_s"]
    assert total == pytest.approx(busy, rel=1e-3)
    assert r["unscoped_s"] < 0.05 * busy
    # the lookup's scan and the step's Pallas kernels sit in their layers
    ops = dict(r["device_ops"])
    assert {"embedding_pool/while", "rowwise_adagrad/rowwise_adagrad_apply",
            "embedding_gather/move_rows"} <= set(ops)
    # the remap holds most of the scan
    assert r["layer_s"]["embedding_remap"] > 0.8 * ops["embedding_pool/while"]
    assert r["backward_s"]["top_mlp"] > 0

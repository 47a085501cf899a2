"""A cell's ``chips`` sets its mesh, and the mesh sets its weights, its
engine, its comparison, its memory reading and the trace planes read.
Four chips are four CPU devices in a process of their own: a copy of
``small-cell`` with ``chips: 4`` (the reduced Qwen3 with four key/value
heads) runs there once, and the tests below read what it printed."""

import json
import os
import subprocess
import sys
import types

import jax
import numpy as np
import pytest

import bench_chip_helpers as H
import harness
import tracereduce as tr

SEED = 2**40 + 9

FOUR_DEVICES = r"""
import json, sys
import jax
import numpy as np
import bench_chip_helpers as H
import harness

root = sys.argv[1]
H.make_checkout(root, chips=4, kv_heads=4)
cell = harness.resolve(root, "small-cell")
ref = harness.plugin(cell, "reference", cell.cfg["model_type"])
one = ref.make_weights(cell.cfg, %(seed)d, H.mesh(1))
four = ref.make_weights(cell.cfg, %(seed)d, harness.cell_mesh(cell))
weights = {}
for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(one),
                        jax.tree.leaves(four)):
    weights[path[-1].key] = {
        "shape": a.shape, "equal": bool(np.array_equal(np.asarray(a),
                                                        np.asarray(b))),
        "devices": len({s.device.id for s in b.addressable_shards}),
        "shards": sorted({s.data.shape for s in b.addressable_shards})}
built = {}
build = harness.build_engine

def spy(*args, **kw):
    engine = build(*args, **kw)
    built.update(getattr(engine._backend, "shard_info", dict)())
    built.update(mesh=dict(engine.mesh.shape),
                 backend=type(engine._backend).__name__,
                 devices=[d.id for d in engine.mesh.devices.flat])
    return engine

harness.build_engine = spy
res = H.run_small(root, seed=2**35 + 17)
print(json.dumps({
    "weights": weights, "engine": built, "correct": res["correct"],
    "checks": res["checks"], "gaps": res["gaps"],
    "attempted": res["attempted"], "failed": res["failed"],
    "memory_peaks": res["memory_peaks"]}))
"""


@pytest.fixture(scope="module")
def four(tmp_path_factory):
    """What a ``chips: 4`` run on four CPU devices printed."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           "PYTHONPATH": os.pathsep.join([os.path.join(H.REPO, "src"),
                                          os.path.dirname(__file__)])}
    root = str(tmp_path_factory.mktemp("four"))
    done = subprocess.run(
        [sys.executable, "-c", FOUR_DEVICES % {"seed": SEED}, root],
        env=env, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-4000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


def test_a_four_chip_cell_serves_on_a_model4_engine_and_is_correct(four):
    eng = four["engine"]
    assert eng["mesh"] == {"data": 1, "model": 4}
    assert eng["backend"] == "ShardedPagedBackend"
    assert eng["model_extent"] == 4 and eng["kv_mode"] == "heads"
    assert eng["kv_heads_per_shard"] == 1
    assert sorted(eng["devices"]) == [0, 1, 2, 3]
    assert four["correct"], four["checks"]
    assert four["gaps"]["program"]["compared_tokens"] >= 40
    assert four["attempted"] > 0 and four["failed"] == 0
    assert sorted(four["memory_peaks"]) == ["0", "1", "2", "3"]


def test_weights_are_bit_identical_on_one_and_four_devices(four):
    assert len(four["weights"]) == 13
    assert all(w["equal"] for w in four["weights"].values()), \
        {k: w for k, w in four["weights"].items() if not w["equal"]}


def test_every_device_holds_a_quarter_of_each_split_weight(four):
    """Every weight of the reduced Qwen3 divides by four along the axis
    the reference names, so no device holds one whole."""
    ref = harness.load_module(os.path.join(H.CHIP, "reference", "qwen3.py"),
                              "ref_qwen3_mesh")
    for name, w in four["weights"].items():
        axis = ref.SPLIT[name] % len(w["shape"])
        want = list(w["shape"])
        want[axis] //= 4
        assert w["devices"] == 4, name
        assert w["shards"] == [want], name


def test_a_one_chip_cell_mesh_is_the_first_device_alone():
    from repro.launch.mesh import make_host_mesh
    mesh = harness.cell_mesh(types.SimpleNamespace(chips=1))
    host = make_host_mesh()
    assert dict(mesh.shape) == dict(host.shape) == {"data": 1, "model": 1}
    assert list(mesh.devices.flat) == list(host.devices.flat) \
        == [jax.devices()[0]]
    assert mesh.axis_types == host.axis_types


def test_weights_are_split_where_the_extent_divides_and_else_whole():
    from jax.sharding import AbstractMesh, AxisType
    ref = harness.load_module(os.path.join(H.CHIP, "reference", "qwen3.py"),
                              "ref_qwen3_split")
    mesh = AbstractMesh((1, 4), ("data", "model"),
                        axis_types=(AxisType.Auto,) * 2)
    abstract = {"embed": jax.ShapeDtypeStruct((1022, 64), np.float32),
                "layers": {"o": jax.ShapeDtypeStruct((2, 128, 64),
                                                     np.float32)}}
    specs = jax.tree.map(lambda s: tuple(s.spec),
                         ref.shardings(abstract, mesh))
    assert specs == {"embed": (None, None),
                     "layers": {"o": (None, "model", None)}}


# ---------------------------------------------------------------------------
# Memory and the trace take the cell's chips
# ---------------------------------------------------------------------------

class _Dev:
    def __init__(self, id, peak):
        self.id, self.peak = id, peak

    def memory_stats(self):
        return None if self.peak is None else {"peak_bytes_in_use": self.peak}


def test_memory_reading_takes_every_chip_and_reports_the_fullest():
    devs = [_Dev(2, 700), _Dev(0, 900), _Dev(3, 800), _Dev(1, 650)]
    mesh = types.SimpleNamespace(devices=np.array(devs).reshape(1, 4))
    peaks = harness.memory_peaks(mesh)
    assert list(peaks.items()) == [(2, 700), (0, 900), (3, 800), (1, 650)]
    assert harness.fullest(peaks) == 900
    assert harness.fullest({0: None}) is None


def _plane(pid, name, line, events):
    """One XPlane in text form: ``events`` as (name, start s, length s)
    on the line ``line``."""
    evs = " ".join(f"events {{ metadata_id: {i + 1} offset_ps: "
                   f"{round(s * 1e12)} duration_ps: {round(d * 1e12)} }}"
                   for i, (_, s, d) in enumerate(events))
    md = " ".join(f'event_metadata {{ key: {i + 1} value {{ id: {i + 1} '
                  f'name: "{n}" }} }}' for i, (n, _, _) in enumerate(events))
    return (f'planes {{ id: {pid} name: "{name}" lines {{ id: 1 '
            f'name: "{line}" timestamp_ns: 0 {evs} }} {md} }} ')


def test_trace_reads_only_the_cells_chips_in_mesh_order(tmp_path):
    """Chips 0-3 are the cell's, busy 1, 2, 3 and 4 s of a 10-s window;
    chip 4 belongs to another process and is busy throughout."""
    from jax.profiler import ProfileData
    text = _plane(1, "/host:CPU", "python", [("bench.traced", 0.0, 10.0)])
    for i in range(5):
        busy = 10.0 if i == 4 else i + 1.0
        text += _plane(10 + i, f"/device:TPU:{i}", "XLA Ops",
                       [(f"%fusion.{i} = f32[8] fusion()", 0.0, busy)])
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    trace = tr.load(str(path), [2, 0, 1, 3])
    assert trace.window == (0.0, 10.0)
    assert [c.ops[0].name for c in trace.chips] == [
        "fusion.2", "fusion.0", "fusion.1", "fusion.3"]
    assert tr.device_busy_s(trace) == pytest.approx((3 + 1 + 2 + 4) / 4)
    assert tr.top_ops(trace) == [["?:fusion.2", pytest.approx(3.0)]]
    one = tr.load(str(path), [0])
    assert [c.ops[0].name for c in one.chips] == ["fusion.0"]
    assert tr.device_busy_s(one) == pytest.approx(1.0)

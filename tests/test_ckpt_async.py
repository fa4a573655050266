"""HProt async checkpoint subsystem (repro.ckpt): parity, delta chains,
integrity verification, crash recovery, lane failure, elastic restore."""
import os
import signal
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ckpt import (AsyncCheckpointManager, CorruptShardError,
                        latest_complete_step)
from repro.hercule.checkpoint import CheckpointManager
from repro.hercule.database import HerculeDB

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _state(step: int):
    """Deterministic, temporally correlated state (recomputable anywhere)."""
    base = np.arange(96 * 32, dtype=np.float32).reshape(96, 32) / 977.0
    return {"params": {"w": jnp.asarray(base * (1.0 + step / 100.0)),
                       "b": jnp.asarray(np.full(32, step, np.float32))},
            "mu": {"w": jnp.asarray(base * 0.01 * step)},
            "step": jnp.int32(step)}


def _template(state):
    dev = jax.devices()[0]
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(
            jnp.shape(x), jnp.result_type(x),
            sharding=jax.sharding.SingleDeviceSharding(dev)), state)


def _assert_tree_equal(got, want, ctx=""):
    flat_g = jax.tree_util.tree_flatten_with_path(got)[0]
    flat_w = jax.tree_util.tree_flatten_with_path(want)[0]
    assert len(flat_g) == len(flat_w)
    for (pg, a), (pw, b) in zip(flat_g, flat_w):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"{ctx}{pg}")


@pytest.mark.parametrize("backend", ["thread", "process"])
def test_async_matches_sync_restore(tmp_path, backend):
    """Full async checkpoint restores to the same bytes as a sync one."""
    state = _state(3)
    sync = CheckpointManager(str(tmp_path / "sync"), ncf=2,
                             async_write=False)
    sync.save(1, state)
    got_sync, _ = sync.restore(_template(state), step=1)
    sync.close()

    amgr = AsyncCheckpointManager(str(tmp_path / "async"), ncf=2,
                                  lane_backend=backend)
    amgr.save(1, state, attrs={"tag": "parity"})
    amgr.wait()
    got_async, attrs = amgr.restore(_template(state), step=1)
    amgr.close()

    assert attrs["tag"] == "parity" and attrs["mode"] == "full"
    _assert_tree_equal(got_async, got_sync, "async-vs-sync ")
    _assert_tree_equal(got_async, state, "async-vs-source ")


def test_delta_chain_bitexact_across_rebase(tmp_path):
    """K=2 deltas restore bit-exactly, including across the full rebase."""
    m = AsyncCheckpointManager(str(tmp_path / "d"), ncf=2, delta_every=2)
    for s in range(1, 6):
        m.save(s, _state(s))
    m.wait()
    # cycle: 1 full, 2-3 delta, 4 full rebase, 5 delta
    modes = {s: m.db.view(s).attrs["mode"] for s in range(1, 6)}
    assert modes == {1: "full", 2: "delta", 3: "delta", 4: "full",
                     5: "delta"}, modes
    w3 = m.db.view(3).record(0, "ckpt/['params']['w']")
    assert w3.codec == "fpdelta-delta" and int(w3.meta["pred_step"]) == 2
    assert "crc32" in w3.meta
    tpl = _template(_state(1))
    for s in range(1, 6):    # every step, either side of the rebase
        got, _ = m.restore(tpl, step=s)
        _assert_tree_equal(got, _state(s), f"step {s} ")
    m.close()


def test_corrupt_shard_raises(tmp_path):
    m = AsyncCheckpointManager(str(tmp_path / "c"), ncf=2)
    m.save(1, _state(1))
    m.wait()
    rec = m.db.view(1).record(0, "ckpt/['params']['w']")
    path = os.path.join(m.db.root, "data", rec.file)
    with open(path, "r+b") as f:    # flip one payload byte
        f.seek(rec.offset + rec.nbytes // 2)
        b = f.read(1)
        f.seek(-1, os.SEEK_CUR)
        f.write(bytes([b[0] ^ 0xFF]))
    with pytest.raises(CorruptShardError, match="CRC32"):
        m.restore(_template(_state(1)), step=1)
    m.close()


def test_latest_step_skips_incomplete(tmp_path):
    """A manifest referencing truncated/missing data loses latest_step."""
    m = AsyncCheckpointManager(str(tmp_path / "t"), ncf=2)
    for s in (1, 2):
        m.save(s, _state(s))
    m.wait()
    assert m.latest_step() == 2
    # truncate the file holding step 2's records below a record extent
    recs = [r for r in m.db.view(2).records]
    path = os.path.join(m.db.root, "data", recs[-1].file)
    with open(path, "r+b") as f:
        f.truncate(recs[-1].offset + recs[-1].nbytes - 1)
    m.db._invalidate_view(2)
    assert m.latest_step() == 1      # newest *complete* step wins
    got, _ = m.restore(_template(_state(1)))
    _assert_tree_equal(got, _state(1))
    m.close()


def test_lane_crash_no_manifest_no_deadlock(tmp_path):
    """A dying writer lane surfaces as an error, leaves no manifest for
    the in-flight step, and never deadlocks wait()."""
    m = AsyncCheckpointManager(str(tmp_path / "k"), ncf=2,
                               lane_backend="process")
    m.save(1, _state(1))
    m.wait()                          # lane exists and step 1 committed
    [proc] = m._backend._procs.values()
    os.kill(proc.pid, signal.SIGKILL)
    proc.join(timeout=10)
    m.save(2, _state(2))
    with pytest.raises(RuntimeError, match="lane"):
        m.wait(timeout=60)
    assert not os.path.exists(
        os.path.join(m.db.root, "ctx_00000002", "MANIFEST.json"))
    assert latest_complete_step(m.db) == 1
    m.close()


_KILL_SNIPPET = """
import os
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import sys; sys.path.insert(0, {src!r})
from test_ckpt_async import _state
from repro.ckpt import AsyncCheckpointManager

m = AsyncCheckpointManager({root!r}, ncf=2, delta_every=2)
for s in (1, 2):
    m.save(s, _state(s))
m.wait()
m.save(3, _state(3))     # still staging/writing when we die
print("SAVED", flush=True)
os._exit(17)
"""


def test_kill_mid_save_recovers_previous_step(tmp_path):
    """Killing the process mid-checkpoint leaves a restorable database:
    either step 3 committed in time, or recovery lands on step 2 —
    never a torn manifest, never garbage."""
    root = str(tmp_path / "kill")
    out = subprocess.run(
        [sys.executable, "-c",
         _KILL_SNIPPET.format(src=SRC, root=root)],
        env={**os.environ, "PYTHONPATH":
             SRC + os.pathsep + os.path.dirname(__file__)},
        cwd=os.path.dirname(__file__),
        capture_output=True, text=True, timeout=300)
    assert out.returncode == 17, (out.returncode, out.stderr[-3000:])
    db = HerculeDB.open(root)
    latest = latest_complete_step(db)
    assert latest in (2, 3), latest
    db.close()
    m = AsyncCheckpointManager(root, ncf=2)    # reopen like a restart
    got, _ = m.restore(_template(_state(latest)), step=latest)
    _assert_tree_equal(got, _state(latest), f"recovered step {latest} ")
    m.close()


_ELASTIC_SAVE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from repro.ckpt import AsyncCheckpointManager

mesh = Mesh(np.array(jax.devices()).reshape(4), ("d",))
sh = NamedSharding(mesh, P("d"))
state = {{
    "w": jax.device_put(jnp.arange(64 * 8, dtype=jnp.float32
                                   ).reshape(64, 8), sh),
    "b": jax.device_put(jnp.arange(128, dtype=jnp.float32) / 128.0, sh),
    "step": jnp.int32(7),
}}
m = AsyncCheckpointManager({root!r}, ncf=2)
m.save(1, state)
m.wait()
n = len(m.db.view(1).records_named("ckpt/['w']"))
m.close()
print("SAVED", n)
"""

_ELASTIC_RESTORE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from repro.ckpt import AsyncCheckpointManager

mesh = Mesh(np.array(jax.devices()).reshape(2), ("d",))
sh = NamedSharding(mesh, P("d"))
template = {{
    "w": jax.ShapeDtypeStruct((64, 8), jnp.float32, sharding=sh),
    "b": jax.ShapeDtypeStruct((128,), jnp.float32, sharding=sh),
    "step": jax.ShapeDtypeStruct((), jnp.int32,
        sharding=jax.sharding.SingleDeviceSharding(jax.devices()[0])),
}}
m = AsyncCheckpointManager({root!r}, ncf=2)
got, _ = m.restore(template, step=1)
assert got["w"].sharding.num_devices == 2, got["w"].sharding
np.testing.assert_array_equal(
    np.asarray(got["w"]),
    np.arange(64 * 8, dtype=np.float32).reshape(64, 8))
np.testing.assert_array_equal(
    np.asarray(got["b"]), np.arange(128, dtype=np.float32) / 128.0)
assert int(got["step"]) == 7
m.close()
print("RESTORED-OK")
"""


def test_elastic_restore_through_async_manager(tmp_path):
    """4-way sharded async save restores onto a 2-device mesh."""
    root = str(tmp_path / "elastic")

    def run(code):
        return subprocess.run([sys.executable, "-c", code],
                              env={**os.environ, "PYTHONPATH": SRC},
                              capture_output=True, text=True, timeout=300)

    out = run(_ELASTIC_SAVE.format(root=root))
    assert out.returncode == 0, out.stderr[-3000:]
    assert "SAVED 4" in out.stdout, out.stdout   # ownership pruning held
    out = run(_ELASTIC_RESTORE.format(root=root))
    assert out.returncode == 0, out.stderr[-3000:]
    assert "RESTORED-OK" in out.stdout


def test_stall_and_metrics_accounting(tmp_path):
    """Spans + metrics cover the save pipeline; stall total accumulates."""
    from repro.obs import TRACER
    TRACER.enable()
    TRACER.clear()
    try:
        m = AsyncCheckpointManager(str(tmp_path / "m"), ncf=2,
                                   delta_every=2)
        for s in (1, 2):
            m.save(s, _state(s))
        m.wait()
        assert m.stall_seconds_total > 0.0
        t = m.telemetry()
        assert t["committed"] == 2 and t["pending"] == 0
        snap = m.obs.snapshot()
        assert snap["ckpt_stall_seconds"]["samples"][0]["value"]["count"] == 2
        assert snap["ckpt_records_total"]["samples"][0]["value"] == 8
        modes = {s["labels"]["mode"]: s["value"]
                 for s in snap["ckpt_saves_total"]["samples"]}
        assert modes == {"full": 1.0, "delta": 1.0}
        m.close()
        names = {s["name"] for s in TRACER.spans()}
        assert {"ckpt.snapshot", "ckpt.stage", "ckpt.write",
                "ckpt.commit"} <= names, names
    finally:
        TRACER.disable()
        TRACER.clear()


# ------------------------------------------------- the budgeted snapshot cut

def _cut_spans(fn):
    from repro.obs.trace import TRACER
    TRACER.enable()
    TRACER.clear()
    try:
        fn()
    finally:
        TRACER.disable()
    return [s["name"] for s in TRACER.spans()]


@pytest.mark.parametrize("budget", [0, 96 * 32 * 4 + 32 * 4])
def test_forced_budget_splits_cut_and_restores(tmp_path, budget):
    """A small forced budget sends what does not fit to the host in the
    stall (both spans present, counter set) and restores bit for bit."""
    state = _state(4)
    m = AsyncCheckpointManager(str(tmp_path / "c"), ncf=2,
                               cut_budget_bytes=budget)

    def save():
        m.save(1, state)
        m.wait()
    names = _cut_spans(save)
    total = sum(np.asarray(x).nbytes for x in jax.tree.leaves(state))
    host = m.telemetry()["cut_host_bytes"]
    assert "ckpt.cut.host" in names and "ckpt.snapshot" in names
    assert ("ckpt.cut.device" in names) == (budget > 0)
    assert host == total - (budget if budget else 0)
    got, _ = m.restore(_template(state), step=1)
    m.close()
    _assert_tree_equal(got, state, "budgeted cut ")


def test_cut_is_donation_safe_on_both_paths(tmp_path):
    """The state's buffers donated and overwritten right after ``save``
    returns: the checkpoint still holds the values at the save, on the
    device path and on the host path of the cut alike."""
    want = jax.tree.map(np.array, _state(5))
    bump = jax.jit(lambda s: jax.tree.map(lambda x: x + 1, s),
                   donate_argnums=0)
    for budget in (None, 96 * 32 * 4):
        m = AsyncCheckpointManager(str(tmp_path / f"d{budget}"), ncf=2,
                                   cut_budget_bytes=budget)
        state = _state(5)
        m.save(1, state)
        state = bump(state)        # donates and overwrites the saved buffers
        state = bump(state)
        m.wait()
        got, _ = m.restore(_template(want), step=1)
        m.close()
        _assert_tree_equal(got, want, f"budget={budget} ")
        assert float(np.asarray(state["step"])) == 7


def test_no_memory_figures_keeps_every_leaf_on_device(tmp_path):
    """On a device without memory figures (the CPU) and no forced budget
    the cut takes today's path: device copies only, nothing to the host."""
    assert jax.devices()[0].memory_stats() is None
    m = AsyncCheckpointManager(str(tmp_path / "n"), ncf=2)

    def save():
        m.save(1, _state(2))
        m.wait()
    names = _cut_spans(save)
    assert "ckpt.cut.device" in names and "ckpt.cut.host" not in names
    assert m.telemetry()["cut_host_bytes"] == 0
    got, _ = m.restore(_template(_state(2)), step=1)
    m.close()
    _assert_tree_equal(got, _state(2), "device cut ")


def test_cut_budget_does_not_ratchet_on_its_own_copies(tmp_path):
    """The budget reads the steps' peak: a later peak raised only by the
    cut's own device copies leaves the budget where it was."""
    class Dev:
        def __init__(self, peak, reserved=1_000 << 20):
            self.peak, self.reserved = peak, reserved

        def memory_stats(self):
            return {"bytes_limit": 10_000 << 20,
                    "peak_bytes_in_use": self.peak,
                    "peak_bytes_reserved": self.reserved}
    m = AsyncCheckpointManager(str(tmp_path / "r"), ncf=2)
    try:
        # live buffers and the programs' reserved temporaries both count
        dev = Dev(5_000 << 20)
        first = m._cut_budget(dev)
        assert first == (4_000 << 20) - m.CUT_MARGIN
        m._cut_device_max[dev] = first     # the cut filled its budget
        dev.peak += first
        assert m._cut_budget(dev) == first
        # steps that grow beyond that do shrink it
        dev.peak += 1_000 << 20
        assert m._cut_budget(dev) == first - (1_000 << 20)
        dev.peak -= 1_000 << 20
        dev.reserved = 3_000 << 20
        assert m._cut_budget(dev) == first - (2_000 << 20)
    finally:
        m.close()
    # two devices: each budget reads its own device's peak and copies
    m = AsyncCheckpointManager(str(tmp_path / "r2"), ncf=2)
    try:
        low, high = Dev(2_000 << 20), Dev(6_000 << 20)
        b_low, b_high = m._cut_budget(low), m._cut_budget(high)
        assert b_low == (7_000 << 20) - m.CUT_MARGIN
        assert b_high == (3_000 << 20) - m.CUT_MARGIN
        m._cut_device_max.update({low: b_low, high: b_high})
        low.peak += b_low
        high.peak += b_high
        assert (m._cut_budget(low), m._cut_budget(high)) == (b_low, b_high)
    finally:
        m.close()


# ------------------------------------------- the raw shard's hand-off

def _odd_state():
    """bfloat16, 0-d and zero-size leaves beside a plain float32 one."""
    return {"bf16": jnp.asarray(np.arange(96 * 32).reshape(96, 32) / 7.0,
                                jnp.bfloat16),
            "count": jnp.int32(-7),
            "empty": jnp.zeros((0, 4), jnp.float32),
            "w": jnp.asarray(np.arange(300, dtype=np.float32) / 3.0)}


def _raw_bytes(state) -> int:
    return sum(np.asarray(x).nbytes for x in jax.tree.leaves(state))


def _zero_copy(m):
    """The manager's zero-copy bytes, from telemetry and from its counter
    (which must agree)."""
    snap = m.obs.snapshot()["ckpt_zero_copy_bytes_total"]["samples"]
    tel = m.telemetry()["zero_copy_bytes"]
    assert snap[0]["value"] == tel
    return tel


def test_raw_shards_reach_thread_lanes_uncopied(tmp_path, monkeypatch):
    """On thread lanes every raw payload the lane writes is the memory
    the gather pulled to the host: no copy in the encode, none in the
    staging area; the counter reads the state's raw bytes."""
    from repro.ckpt import lanes
    pulled, written = {}, {}
    write_shard = lanes._write_shard

    def spy_write(db, snap):
        written[snap.meta["rec_name"]] = snap.arrays["payload"]
        return write_shard(db, snap)
    monkeypatch.setattr(lanes, "_write_shard", spy_write)
    state = _odd_state()
    m = AsyncCheckpointManager(str(tmp_path / "z"), ncf=2)
    encode = m._encode

    def spy_encode(name, domain, data, *, full):
        pulled[f"ckpt/{name}"] = data
        return encode(name, domain, data, full=full)
    m._encode = spy_encode
    m.save(1, state)
    m.wait()
    assert sorted(written) == sorted(pulled) and len(pulled) == 4
    for name, host in pulled.items():
        assert m.db.view(1).record(0, name).codec == "raw"
        if host.nbytes:
            assert np.shares_memory(written[name], host), name
    assert _zero_copy(m) == _raw_bytes(state)
    m.close()


@pytest.mark.parametrize("backend", ["thread", "process"])
def test_odd_leaves_restore_bitexact(tmp_path, backend):
    """bfloat16, 0-d int32 and zero-size leaves go out raw and restore
    bit for bit on both lane backends; only a thread lane holds the
    payload by reference, a process lane copies it into shared memory."""
    state = _odd_state()
    m = AsyncCheckpointManager(str(tmp_path / backend), ncf=2,
                               lane_backend=backend)
    m.save(1, state)
    m.wait()
    got, _ = m.restore(_template(state), step=1)
    assert _zero_copy(m) == (_raw_bytes(state) if backend == "thread"
                             else 0)
    m.close()
    for k in state:
        assert got[k].dtype == state[k].dtype and \
            got[k].shape == state[k].shape, k
    _assert_tree_equal(got, state, f"{backend} ")


def test_delta_over_raw_picks_the_smaller_codec(tmp_path):
    """A delta save over a raw one writes ``fpdelta-delta`` where the
    residual is smaller than the raw bytes and raw where it is not
    (fresh noise); only the raw shards count as zero-copy."""
    rng = np.random.default_rng(11)

    def state(step):
        return {**_state(step),
                "noise": jnp.asarray(rng.standard_normal(4096, np.float32))}
    s1, s2 = state(1), state(2)
    m = AsyncCheckpointManager(str(tmp_path / "d"), ncf=2, delta_every=1)
    m.save(1, s1)
    m.save(2, s2)
    m.wait()
    codecs = {r.name: r.codec for r in m.db.view(2).records}
    assert codecs["ckpt/['params']['w']"] == "fpdelta-delta"
    assert codecs["ckpt/['mu']['w']"] == "fpdelta-delta"
    assert codecs["ckpt/['noise']"] == "raw"
    assert codecs["ckpt/['step']"] == "raw"         # an integer leaf
    delta = {"params", "mu"}
    raw2 = sum(np.asarray(x).nbytes for k, v in s2.items()
               if k not in delta for x in jax.tree.leaves(v))
    raw2 += np.asarray(s2["params"]["b"]).nbytes    # 32 floats: < 64
    assert _zero_copy(m) == _raw_bytes(s1) + raw2
    tpl = _template(s1)
    for step, want in ((1, s1), (2, s2)):
        got, _ = m.restore(tpl, step=step)
        _assert_tree_equal(got, want, f"step {step} ")
    m.close()

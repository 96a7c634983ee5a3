"""bigdl_tpu.checkpoint — async fault-tolerant checkpointing tests.

The ISSUE-7 acceptance surface:
- snapshot format: atomic commit, CRC32c manifest, data-only npz,
  read-manifest/verify without loading arrays;
- discovery: corrupt/torn snapshots are SKIPPED, never loaded;
- retention: keep_last ring + keep_every pins;
- THE CRASH/RESUME GATE: train N steps straight vs train-with-kill +
  resume → bitwise-identical loss sequences and final params, K∈{1,4},
  grad_sync on/off — in-process (fresh-object resume and the
  DistriOptimizer retry loop) plus REAL subprocess fault injection
  (SIGKILL mid-epoch, SIGTERM preemption → final snapshot + clean
  exit);
- async inertness: checkpointing on adds zero dispatches and the loss
  sequence stays bitwise identical;
- schema validation: grad_sync flips / bucket-plan drift /
  architecture drift fail loudly with a diff;
- shim back-compat + the now-real non-overwrite path;
- tools/ckpt_inspect.py CLI.
"""

import os
import re
import signal
import subprocess
import sys
import time
import zipfile

import jax
import numpy as np
import pytest

import ckpt_train_child as child_mod
from bigdl_tpu import nn, optim
from bigdl_tpu.checkpoint import (AsyncSnapshotWriter, CheckpointManager,
                                  PreemptionHandler, SchemaMismatchError,
                                  SnapshotError, build_schema,
                                  load_snapshot, read_manifest,
                                  verify_snapshot, write_snapshot)
from bigdl_tpu.dataset.dataset import (DistributedDataSet, LocalDataSet,
                                       TransformedDataSet)
from bigdl_tpu.optim.optimizer import LocalOptimizer

HERE = os.path.dirname(os.path.abspath(__file__))
CHILD = os.path.join(HERE, "ckpt_train_child.py")


# ---------------------------------------------------------------- helpers
class Rec:
    """TrainSummary stand-in capturing the per-iteration replay."""

    def __init__(self):
        self.rows = []  # (step, loss)

    def add_train_step(self, step, loss, lr, throughput):
        self.rows.append((step, loss))

    def add_scalar(self, tag, value, step):
        pass

    def trigger_for(self, name):
        return None

    @property
    def losses(self):
        return np.array([l for _, l in self.rows])

    @property
    def steps(self):
        return [s for s, _ in self.rows]

    def by_step(self):
        """step → loss, LAST occurrence winning (a crashed-then-retried
        run replays some iterations; the retried values are the ones
        that produced the final params)."""
        return dict(self.rows)


def build_opt(ckpt_dir=None, iters=16, k=4, every=3, grad_sync=None,
              distri=False, rec=None, **distri_kw):
    cls = optim.DistriOptimizer if distri else LocalOptimizer
    kw = dict(distri_kw)
    if distri and grad_sync is not None:
        kw["grad_sync"] = grad_sync
    opt = (cls(child_mod.mlp(), child_mod.pipeline(),
               nn.ClassNLLCriterion(), **kw)
           .set_optim_method(optim.Adam(1e-3))
           .set_steps_per_dispatch(k)
           .set_seed(7)
           .set_end_when(optim.max_iteration(iters)))
    if rec is not None:
        opt.set_train_summary(rec)
    if ckpt_dir:
        opt.set_checkpoint(ckpt_dir, optim.several_iteration(every))
    return opt


def reference_run(iters=16, k=4, every=3, grad_sync=None, distri=False,
                  **distri_kw):
    rec = Rec()
    opt = build_opt(iters=iters, k=k, grad_sync=grad_sync, distri=distri,
                    rec=rec, **distri_kw)
    # same trigger cadence as the checkpointed runs (it shapes block
    # planning — a firing iteration always ends a block) but no path,
    # so the reference shares the EXACT scan partitioning and the
    # bitwise comparison isolates the save/resume machinery
    opt.checkpoint_trigger = optim.several_iteration(every)
    opt.optimize()
    return rec, opt


def assert_params_equal(a, b):
    for x, y in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def flaky_lr(opt, crash_at):
    """Raise once on the ``crash_at``-th host LR computation — the
    fault-injection shape test_training already uses."""
    real = opt.optim_method.current_lr
    calls = {"n": 0}

    def lr(it, ep, metric=None):
        calls["n"] += 1
        if calls["n"] == crash_at:
            raise RuntimeError("injected mid-epoch failure")
        return real(it, ep, metric)

    opt.optim_method.current_lr = lr


# ========================================================== snapshot layer
class TestSnapshotFormat:
    def test_roundtrip_manifest_and_schema_hash(self, tmp_path):
        params = {"layer": {"w": np.arange(6, dtype=np.float32)
                            .reshape(2, 3)},
                  "pair": (np.zeros(2), [np.ones(3), 5]),
                  "bf": jax.numpy.arange(4, dtype=jax.numpy.bfloat16)}
        schema = build_schema(params, optim_method="Adam")
        f = write_snapshot(str(tmp_path / "model.3"), params=params,
                           opt_state={"m": np.ones(3), "step": 7},
                           driver_state={"neval": 3, "epoch": 1,
                                         "loss": 0.5},
                           run_state={"seed": 7,
                                      "dataset_position":
                                          {"shuffle_epoch": 1}},
                           step=3, schema=schema)
        m = read_manifest(f)
        assert m["format"] == "bigdl_tpu-snapshot" and m["version"] == 3
        assert m["step"] == 3 and m["epoch"] == 1
        assert len(m["schema_hash"]) == 12
        assert m["total_bytes"] == sum(e["nbytes"] for e in m["arrays"])
        ok, detail = verify_snapshot(f)
        assert ok, detail
        blob = load_snapshot(f)
        assert blob["params"]["bf"].dtype == jax.numpy.bfloat16
        assert isinstance(blob["params"]["pair"], tuple)
        assert blob["run"]["dataset_position"] == {"shuffle_epoch": 1}
        assert blob["manifest"]["schema"]["optim_method"] == "Adam"
        # data-only: plain zip, loads with pickle OFF
        assert zipfile.is_zipfile(f)
        with np.load(f, allow_pickle=False) as z:
            assert "__manifest__" in z.files

    def test_atomic_commit_leaves_no_tmp(self, tmp_path):
        f = write_snapshot(str(tmp_path / "model.1"),
                           params={"w": np.ones(8)}, step=1)
        assert os.path.exists(f)
        assert not os.path.exists(f + ".tmp")

    def test_overwrite_false_raises(self, tmp_path):
        f = str(tmp_path / "model.2")
        write_snapshot(f, params={"w": np.ones(2)}, step=2)
        with pytest.raises(FileExistsError, match="overWriteCheckpoint"):
            write_snapshot(f, params={"w": np.zeros(2)}, step=2,
                           overwrite=False)
        # overwrite=True replaces
        write_snapshot(f, params={"w": np.zeros(2)}, step=2)
        assert float(np.asarray(load_snapshot(f)["params"]["w"]).sum()) \
            == 0.0


def _corrupt_array_byte(path, member="a0.npy"):
    """Flip one byte inside a member's DATA region (the .npy payload is
    located via its magic + header length, so the flip lands in payload
    bytes, not in zip/npy framing)."""
    zi = zipfile.ZipFile(path).getinfo(member)
    raw = bytearray(open(path, "rb").read())
    pos = raw.find(b"\x93NUMPY", zi.header_offset)
    assert pos != -1
    hlen = int.from_bytes(raw[pos + 8:pos + 10], "little")
    raw[pos + 10 + hlen + 2] ^= 0x01
    open(path, "wb").write(bytes(raw))


class TestIntegrityAndDiscovery:
    def _write(self, d, step, fill=1.0):
        return write_snapshot(os.path.join(d, f"model.{step}"),
                              params={"w": np.full(64, fill, np.float32)},
                              step=step)

    def test_bit_flip_detected_skipped_never_loaded(self, tmp_path):
        d = str(tmp_path)
        self._write(d, 2)
        bad = self._write(d, 4)
        _corrupt_array_byte(bad)
        ok, detail = verify_snapshot(bad)
        assert not ok and "crc" in detail.lower()
        with pytest.raises(SnapshotError, match="refusing to load"):
            load_snapshot(bad)
        mgr = CheckpointManager(d)
        assert mgr.latest_valid() == os.path.join(d, "model.2")

    def test_meta_member_corruption_detected_and_skipped(self, tmp_path):
        """A bit-flip in the __meta__ skeleton (not an array) must fail
        verification exactly like array corruption — otherwise the
        latest-VALID fallback would hand np.load a corrupt file and the
        retry loop would crash instead of falling back."""
        d = str(tmp_path)
        good = self._write(d, 2)
        bad = self._write(d, 6)
        _corrupt_array_byte(bad, member="__meta__.npy")
        ok, detail = verify_snapshot(bad)
        assert not ok, detail
        with pytest.raises(SnapshotError):
            load_snapshot(bad)
        assert CheckpointManager(d).latest_valid() == good

    def test_torn_write_skipped(self, tmp_path):
        d = str(tmp_path)
        good = self._write(d, 3)
        raw = open(good, "rb").read()
        open(os.path.join(d, "model.9"), "wb").write(raw[:len(raw) // 2])
        ok, detail = verify_snapshot(os.path.join(d, "model.9"))
        assert not ok
        assert CheckpointManager(d).latest_valid() == good

    def test_foreign_and_garbage_files_ignored(self, tmp_path):
        d = str(tmp_path)
        good = self._write(d, 1)
        open(os.path.join(d, "model.zzz"), "w").write("not a step")
        np.savez(os.path.join(d, "model.5"), foreign=np.ones(3))
        os.replace(os.path.join(d, "model.5.npz"),
                   os.path.join(d, "model.5"))
        mgr = CheckpointManager(d)
        assert mgr.latest_valid() == good

    def test_legacy_v2_without_manifest_still_loads(self, tmp_path):
        import json
        from bigdl_tpu.checkpoint.snapshot import encode_tree
        arrays = []
        sk = {"version": 2, "params": encode_tree({"w": np.ones(2)},
                                                  arrays),
              "model_state": None, "opt_state": None,
              "driver_state": {"neval": 4}}
        path = str(tmp_path / "model.4")
        with open(path, "wb") as f:
            np.savez(f, __meta__=np.frombuffer(
                json.dumps(sk).encode(), dtype=np.uint8),
                **{f"a{i}": a for i, a in enumerate(arrays)})
        ok, detail = verify_snapshot(path)
        assert ok and "legacy" in detail
        blob = load_snapshot(path)
        assert blob["driver_state"]["neval"] == 4
        assert blob["manifest"] is None
        assert CheckpointManager(str(tmp_path)).latest_valid() == path


class TestManagerRetentionAndWriter:
    def _save(self, mgr, step):
        mgr.save(step, {"w": np.full(4, step, np.float32)},
                 driver_state={"neval": step}, sync=True)

    def test_keep_last_ring(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), keep_last=2,
                                async_save=False)
        for s in (1, 2, 3, 4, 5):
            self._save(mgr, s)
        assert mgr.steps() == [4, 5]

    def test_keep_every_pins_sparse_archive(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), keep_last=2, keep_every=3,
                                async_save=False)
        for s in range(1, 9):
            self._save(mgr, s)
        assert mgr.steps() == [3, 6, 7, 8]

    def test_async_commits_in_order_and_drains(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), keep_last=10)
        for s in (1, 2, 3):
            mgr.save(s, {"w": np.full(4, s, np.float32)},
                     driver_state={"neval": s})
        mgr.wait()
        assert mgr.steps() == [1, 2, 3]
        blob = mgr.restore()
        assert blob["driver_state"]["neval"] == 3

    def test_writer_error_surfaces_on_drain(self):
        w = AsyncSnapshotWriter()

        def boom():
            raise OSError("disk full")

        w.submit(boom)
        with pytest.raises(RuntimeError, match="NOT durably saved"):
            w.drain()

    def test_writer_bounded_backpressure(self):
        import threading
        gate = threading.Event()
        w = AsyncSnapshotWriter(capacity=1)
        w.submit(gate.wait)  # occupies the worker
        w.submit(lambda: None)  # fills the queue
        t0 = time.perf_counter()
        t = threading.Thread(target=lambda: w.submit(lambda: None))
        t.start()
        t.join(timeout=0.2)
        assert t.is_alive()  # third submit blocks — bounded
        gate.set()
        t.join(timeout=5.0)
        assert not t.is_alive()
        w.close()
        assert time.perf_counter() - t0 < 10


# ===================================================== dataset positioning
class TestDatasetPosition:
    def test_local_dataset_epoch_keyed_restore(self):
        a = LocalDataSet(list(range(20)), seed=3)
        for _ in range(4):
            a.shuffle()
        b = LocalDataSet(list(range(20)), seed=3)
        b.restore_position(a.position_state())
        assert list(b._indexes) == list(a._indexes)
        assert sorted(b._indexes) == list(range(20))  # a permutation

    def test_epoch_zero_is_insertion_order(self):
        a = LocalDataSet(list(range(5)), seed=1)
        a.restore_position({"shuffle_epoch": 0})
        assert list(a._indexes) == [0, 1, 2, 3, 4]

    def test_transformed_dataset_delegates(self):
        from bigdl_tpu.dataset.transformer import Transformer

        class Ident(Transformer):
            def __call__(self, it):
                return it

        base = LocalDataSet(list(range(8)), seed=2)
        ds = TransformedDataSet(base, Ident())
        ds.shuffle()
        st = ds.position_state()
        assert st == {"shuffle_epoch": 1}
        ds.restore_position({"shuffle_epoch": 0})
        assert base._epoch == 0

    def test_distributed_dataset_restore(self):
        a = DistributedDataSet(list(range(16)), seed=3, process_index=0,
                               process_count=2)
        a.shuffle(), a.shuffle()
        b = DistributedDataSet(list(range(16)), seed=3, process_index=0,
                               process_count=2)
        b.restore_position(a.position_state())
        assert np.array_equal(a._global_indexes, b._global_indexes)


# ================================================= THE CRASH/RESUME GATES
class TestResumeBitwiseInProcess:
    """Emulated kill (exception mid-epoch) + fresh-object resume must be
    bitwise-identical to the uninterrupted run — K∈{1,4}, grad_sync
    on/off.  The subprocess class below repeats this with REAL kills."""

    def _splice_check(self, ref_rec, ref_opt, crashed, resumed_rec,
                      resumed_opt, iters):
        ref = ref_rec.by_step()
        got = dict(crashed.by_step())
        got.update(resumed_rec.by_step())
        assert sorted(got) == list(range(1, iters + 1))
        for s in got:
            assert got[s] == ref[s], (s, got[s], ref[s])
        assert_params_equal(ref_opt.model._params,
                            resumed_opt.model._params)

    @pytest.mark.parametrize("k", [1, 4])
    def test_local_kill_and_fresh_resume(self, k, tmp_path):
        iters = 16  # 10-step epochs: the crash AND the resume are
        ref_rec, ref_opt = reference_run(iters=iters, k=k)  # mid-epoch
        d = str(tmp_path / f"ck{k}")
        crashed = Rec()
        opt = build_opt(d, iters=iters, k=k, rec=crashed)
        flaky_lr(opt, crash_at=9)
        with pytest.raises(RuntimeError, match="injected"):
            opt.optimize()
        resumed = Rec()
        opt2 = build_opt(d, iters=iters, k=k, rec=resumed)
        assert opt2.resume()
        opt2.optimize()
        assert resumed.steps[0] > 1  # really resumed, not restarted
        self._splice_check(ref_rec, ref_opt, crashed, resumed, opt2,
                           iters)

    @pytest.mark.parametrize("k,grad_sync", [(1, True), (4, True),
                                             (4, False)])
    def test_distri_retry_loop_resumes_bitwise(self, k, grad_sync,
                                               tmp_path, devices):
        """The DistriOptimizer failure-retry loop (now manager-backed:
        latest-VALID discovery + full-state restore incl. the ZeRO-1
        masters and shuffle position) must land on the uninterrupted
        trajectory bitwise."""
        iters = 12
        ref_rec, ref_opt = reference_run(iters=iters, k=k, distri=True,
                                         grad_sync=grad_sync)
        rec = Rec()
        opt = build_opt(str(tmp_path / "ck"), iters=iters, k=k,
                        distri=True, grad_sync=grad_sync, rec=rec)
        flaky_lr(opt, crash_at=8)
        opt.optimize()  # crashes mid-epoch, retries from model.6
        assert opt.state["neval"] == iters
        ref = ref_rec.by_step()
        got = rec.by_step()
        assert sorted(got) == list(range(1, iters + 1))
        for s in got:
            assert got[s] == ref[s], (s, got[s], ref[s])
        assert_params_equal(ref_opt.model._params, opt.model._params)

    def test_retry_skips_corrupt_latest_snapshot(self, tmp_path,
                                                 devices):
        """Crash → corrupt the newest snapshot → retry must fall back
        to the previous VALID one and still finish on the reference
        trajectory (resuming from an earlier step recomputes the same
        values bitwise)."""
        iters = 12
        _, ref_opt = reference_run(iters=iters, k=4, distri=True)
        d = str(tmp_path / "ck")
        opt = build_opt(d, iters=iters, k=4, distri=True)
        real_impl = opt._optimize_impl
        calls = {"n": 0}

        def impl():
            calls["n"] += 1
            if calls["n"] == 2:
                # between crash and retry: newest snapshot goes bad
                mgr = opt._checkpoint_manager()
                _corrupt_array_byte(mgr.path_for(max(mgr.steps())))
            return real_impl()

        opt._optimize_impl = impl
        flaky_lr(opt, crash_at=8)
        opt.optimize()
        assert opt.state["neval"] == iters
        assert_params_equal(ref_opt.model._params, opt.model._params)

    def test_resume_from_epoch_boundary_snapshot(self, tmp_path):
        """A snapshot taken at the epoch-rollover iteration (neval=10,
        records reset to 0, shuffle already advanced) must resume with
        the epoch-1 permutation and zero fast-forward — the rollover/
        checkpoint ordering inside _replay_block is what this pins."""
        iters = 16
        ref_rec, ref_opt = reference_run(iters=iters, k=4, every=5)
        d = str(tmp_path / "ck")
        crashed = Rec()
        opt = build_opt(d, iters=iters, k=4, every=5, rec=crashed)
        flaky_lr(opt, crash_at=12)
        with pytest.raises(RuntimeError):
            opt.optimize()
        resumed = Rec()
        opt2 = build_opt(d, iters=iters, k=4, every=5, rec=resumed)
        assert opt2.resume()
        assert opt2.state["neval"] == 10
        assert opt2.state["records_processed_this_epoch"] == 0
        assert opt2.state["epoch"] == 1
        opt2.optimize()
        self._splice_check(ref_rec, ref_opt, crashed, resumed, opt2,
                           iters)

    def test_resume_crosses_epoch_boundary_with_restored_shuffle(
            self, tmp_path):
        """Kill in epoch 0, resume, run through the epoch-1 shuffle:
        the restored run must re-derive the SAME epoch-1 permutation
        (epoch-keyed shuffle) — any drift shows up as a loss
        mismatch."""
        iters = 25  # crosses shuffles at 10 and 20
        ref_rec, ref_opt = reference_run(iters=iters, k=4)
        d = str(tmp_path / "ck")
        crashed = Rec()
        opt = build_opt(d, iters=iters, k=4, rec=crashed)
        flaky_lr(opt, crash_at=8)
        with pytest.raises(RuntimeError):
            opt.optimize()
        resumed = Rec()
        opt2 = build_opt(d, iters=iters, k=4, rec=resumed)
        assert opt2.resume()
        opt2.optimize()
        self._splice_check(ref_rec, ref_opt, crashed, resumed, opt2,
                           iters)


def _wait_for_step(losses_path, step, proc, timeout=90):
    t0 = time.time()
    while time.time() - t0 < timeout:
        if os.path.exists(losses_path):
            lines = open(losses_path).read().splitlines()
            if lines and int(lines[-1].split()[0]) >= step:
                return
        if proc.poll() is not None:
            raise AssertionError(
                "child exited before reaching step "
                f"{step}:\n{proc.stderr.read().decode()[-2000:]}")
        time.sleep(0.05)
    proc.kill()
    raise AssertionError(f"child never reached step {step}")


def _wait_for_commit(ckpt_dir, proc, timeout=90):
    """Wait until at least one snapshot has COMMITTED (a ``model.N``
    file, not a ``.tmp``).  The async writer trails the driver loop, so
    'the loss log passed step 8' does not imply 'model.3 is on disk' —
    killing in that gap leaves the resume child nothing valid and the
    test flakes on writer-thread scheduling instead of testing the
    resume path (a latent race surfaced by the obs-plane PR's timing
    shifts)."""
    t0 = time.time()
    while time.time() - t0 < timeout:
        if os.path.isdir(ckpt_dir) and any(
                re.fullmatch(r"model\.\d+", f)
                for f in os.listdir(ckpt_dir)):
            return
        if proc.poll() is not None:
            return  # a finished child drained its writer — committed
        time.sleep(0.02)
    proc.kill()
    raise AssertionError("no snapshot ever committed")


def _parse_losses(path):
    out = {}
    for line in open(path).read().splitlines():
        s, l = line.split()
        out[int(s)] = float(l)
    return out


def _run_child(args, wait=True):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.dirname(HERE) + os.pathsep \
        + env.get("PYTHONPATH", "")
    proc = subprocess.Popen(
        [sys.executable, CHILD] + args, cwd=os.path.dirname(HERE),
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE)
    if not wait:
        return proc
    out, err = proc.communicate(timeout=300)
    assert proc.returncode == 0, err.decode()[-2000:]
    return out.decode()


class TestSubprocessFaultInjection:
    """REAL kills: a child process training with checkpointing is
    SIGKILLed mid-epoch (or SIGTERM-preempted) and a second child
    resumes — the spliced loss sequence and the final params must equal
    the uninterrupted reference bitwise.  Kept lean (one reference per
    config, children share the tiny-MLP recipe) to stay well under the
    ~30s budget."""

    def _reference(self, iters, k, every=3):
        rec, opt = reference_run(iters=iters, k=k, every=every)
        return rec.by_step(), opt

    def _check_against_reference(self, ref, ref_opt, losses_a, losses_b,
                                 params_npz, iters):
        a, b = _parse_losses(losses_a), _parse_losses(losses_b)
        assert min(b) > 1 and max(b) == iters  # resumed, not restarted
        combined = dict(a)
        combined.update(b)
        assert sorted(combined) == list(range(1, iters + 1))
        for s, l in combined.items():
            assert l == ref[s], (s, l, ref[s])
        with np.load(params_npz) as z:
            got = [z[f"p{i}"] for i in range(len(z.files))]
        for x, y in zip(jax.tree_util.tree_leaves(ref_opt.model._params),
                        got):
            np.testing.assert_array_equal(np.asarray(x), y)

    @pytest.mark.parametrize("k", [1, 4])
    def test_sigkill_mid_epoch_resumes_bitwise(self, k, tmp_path):
        iters = 16
        ref, ref_opt = self._reference(iters, k)
        d = str(tmp_path / "ck")
        la, lb = str(tmp_path / "a.txt"), str(tmp_path / "b.txt")
        pout = str(tmp_path / "params.npz")
        proc = _run_child(["--dir", d, "--losses", la, "--iters",
                           str(iters), "--k", str(k)], wait=False)
        try:
            _wait_for_step(la, 8, proc)  # past model.6, mid-epoch
            _wait_for_commit(d, proc)  # ... and ≥1 snapshot ON DISK
        finally:
            proc.kill()
        proc.wait(timeout=30)
        _run_child(["--dir", d, "--losses", lb, "--iters", str(iters),
                    "--k", str(k), "--resume", "--params-out", pout])
        self._check_against_reference(ref, ref_opt, la, lb, pout, iters)

    def test_sigterm_preemption_final_snapshot_then_resume(self,
                                                           tmp_path):
        """SIGTERM → the child finishes the in-flight block, writes a
        final snapshot, exits 0 (clean preemption); the resume child
        continues to a bitwise-identical end state."""
        # long enough that SIGTERM lands while the driver loop is live
        # (a finished run uninstalls the handler and would die with
        # the default action — that would be a -15 exit, caught below)
        iters, k = 150, 4
        ref, ref_opt = self._reference(iters, k, every=1000)
        d = str(tmp_path / "ck")
        la, lb = str(tmp_path / "a.txt"), str(tmp_path / "b.txt")
        pout = str(tmp_path / "params.npz")
        proc = _run_child(["--dir", d, "--losses", la, "--iters",
                           str(iters), "--k", str(k), "--preemption",
                           # sparse trigger: the final snapshot is the
                           # preemption path's own work, not a trigger's
                           "--every", "1000"], wait=False)
        _wait_for_step(la, 5, proc)
        proc.send_signal(signal.SIGTERM)
        out, err = proc.communicate(timeout=60)
        assert proc.returncode == 0, err.decode()[-2000:]
        assert b"PREEMPTED" in out, out
        n_final = int(out.split()[-1])
        snaps = CheckpointManager(d).steps()
        assert snaps == [n_final]  # exactly the preemption snapshot
        _run_child(["--dir", d, "--losses", lb, "--iters", str(iters),
                    "--k", str(k), "--resume", "--params-out", pout])
        self._check_against_reference(ref, ref_opt, la, lb, pout, iters)


class TestPreemptionInProcess:
    def test_request_finishes_block_snapshots_and_exits_cleanly(
            self, tmp_path):
        d = str(tmp_path / "ck")
        rec = Rec()
        opt = build_opt(d, iters=50, k=4, every=100, rec=rec) \
            .set_preemption_handling()
        orig = rec.add_train_step

        def hook(step, loss, lr, thr):
            orig(step, loss, lr, thr)
            if step == 7:
                opt._preemption.request()

        rec.add_train_step = hook
        opt.optimize()
        assert opt.state.get("preempted") is True
        n = opt.state["neval"]
        assert 7 <= n < 50  # stopped at the next block boundary
        assert rec.steps == list(range(1, n + 1))  # in-flight replayed
        mgr = CheckpointManager(d)
        assert mgr.steps() == [n]
        blob = mgr.restore()
        assert blob["driver_state"]["neval"] == n
        assert "preempted" not in blob["driver_state"]

    def test_preempted_flag_cleared_on_next_run(self, tmp_path):
        """A later optimize() on the same optimizer must not report a
        phantom preemption — nor bake one into its checkpoints'
        driver_state."""
        d = str(tmp_path / "ck")
        rec = Rec()
        opt = build_opt(d, iters=50, k=4, every=100, rec=rec) \
            .set_preemption_handling()
        orig = rec.add_train_step

        def hook(step, loss, lr, thr):
            orig(step, loss, lr, thr)
            if step == 7 and not opt.state.get("preempted"):
                opt._preemption.request()

        rec.add_train_step = hook
        opt.optimize()
        assert opt.state.get("preempted") is True
        opt.optimize()  # continue in-process to completion
        assert opt.state["neval"] == 50
        assert "preempted" not in opt.state
        blob = CheckpointManager(d).restore()
        assert "preempted" not in blob["driver_state"]

    def test_no_redundant_final_snapshot_when_trigger_just_fired(
            self, tmp_path):
        """Preemption landing on an iteration a trigger checkpoint just
        covered must not write (or collide on) a second model.<N> —
        even with over_write_checkpoint(False)."""
        d = str(tmp_path / "ck")
        rec = Rec()
        opt = build_opt(d, iters=50, k=4, every=4, rec=rec) \
            .set_preemption_handling().over_write_checkpoint(False)
        orig = rec.add_train_step

        def hook(step, loss, lr, thr):
            orig(step, loss, lr, thr)
            if step == 4:
                opt._preemption.request()

        rec.add_train_step = hook
        opt.optimize()  # must NOT raise FileExistsError
        assert opt.state.get("preempted") is True
        assert opt.state["neval"] == 4
        assert CheckpointManager(d).steps() == [4]

    def test_set_checkpoint_reconfigure_stops_old_writer(self, tmp_path):
        opt = build_opt(str(tmp_path / "a"), iters=4, k=4, every=2)
        opt.optimize()
        old = opt._ckpt_manager
        thread = old._writer._thread
        assert thread is not None and thread.is_alive()
        opt.set_checkpoint(str(tmp_path / "b"),
                           optim.several_iteration(2))
        assert not thread.is_alive()  # no stranded daemon per reconfig
        assert opt._ckpt_manager is None

    def test_handler_installs_and_restores_signal_handlers(self):
        prev = signal.getsignal(signal.SIGTERM)
        with PreemptionHandler() as h:
            assert h.installed
            assert signal.getsignal(signal.SIGTERM) == h._on_signal
            os.kill(os.getpid(), signal.SIGTERM)
            for _ in range(100):
                if h.triggered:
                    break
                time.sleep(0.01)
            assert h.triggered and h.signum == signal.SIGTERM
        assert signal.getsignal(signal.SIGTERM) == prev


# ======================================================== async inertness
class TestAsyncInertness:
    def test_checkpointing_adds_zero_dispatches_and_keeps_loss_bitwise(
            self, monkeypatch, tmp_path):
        """The counting-wrapper gate: checkpointing enabled (async)
        must not change the dispatch count and the loss sequence stays
        bitwise identical — the save path never touches the device
        beyond the replay-boundary D2H capture.

        Minimal pair: the checkpoint TRIGGER legitimately shapes block
        planning (a firing iteration always ends a block), so the
        baseline run keeps the SAME trigger wired for probing but no
        checkpoint path — the only delta between the runs is the save
        path itself."""
        calls = {"n": 0}
        orig = LocalOptimizer._build_block_fn

        def counting(self, grad_fn, kk):
            fn = orig(self, grad_fn, kk)

            def wrapped(*a, **kw):
                calls["n"] += 1
                return fn(*a, **kw)

            return wrapped

        monkeypatch.setattr(LocalOptimizer, "_build_block_fn", counting)
        runs = {}
        for mode in ("off", "on"):
            calls["n"] = 0
            rec = Rec()
            opt = build_opt(str(tmp_path / "ck") if mode == "on"
                            else None, iters=16, k=4, every=3, rec=rec)
            if mode == "off":
                # same probe cadence, no path → no saves
                opt.checkpoint_trigger = optim.several_iteration(3)
            opt.optimize()
            runs[mode] = (rec, calls["n"])
        (rec_off, n_off), (rec_on, n_on) = runs["off"], runs["on"]
        assert n_on == n_off
        np.testing.assert_array_equal(rec_off.losses, rec_on.losses)
        assert CheckpointManager(str(tmp_path / "ck")).steps()  # saved

    def test_metrics_and_telemetry_span_recorded(self, tmp_path):
        opt = build_opt(str(tmp_path / "ck"), iters=8, k=4)
        opt.set_telemetry(True)
        opt.optimize()
        snap = opt.telemetry_snapshot()
        hists = snap["histograms"]
        assert hists["checkpoint/driver_stall_s"]["count"] == 2
        assert hists["checkpoint/save_s"]["count"] == 2
        assert snap["counters"]["checkpoint/snapshots_committed"] == 2
        assert snap["counters"]["checkpoint/bytes_written"] > 0
        assert 0.0 <= snap["gauges"]["checkpoint/stall_fraction"] < 1.0
        names = [e[1] for e in opt._telemetry.tracer.events()]
        assert "checkpoint" in names

    def test_async_driver_stall_much_smaller_than_write(self, tmp_path):
        """The point of async: the driver-side stall per snapshot is a
        fraction of the full serialize+CRC+fsync the writer thread
        pays.  (No benchmark cell checkpoints yet; this just pins the
        ordering so a regression that moves the write back inline
        fails loudly.)"""
        opt = build_opt(str(tmp_path / "ck"), iters=12, k=4, every=2)
        opt.optimize()
        reg = opt.metrics.registry
        drv = reg.get("checkpoint/driver_stall_s")
        save = reg.get("checkpoint/save_s")
        assert drv.count == save.count >= 5
        assert drv.mean < save.mean, (drv.mean, save.mean)


# ======================================================= schema validation
class TestSchemaValidation:
    def _train_distri(self, d, devices, **kw):
        opt = build_opt(d, iters=4, k=4, every=2, distri=True, **kw)
        opt.optimize()
        return opt

    def test_grad_sync_flip_fails_with_diff(self, tmp_path, devices):
        d = str(tmp_path / "ck")
        self._train_distri(d, devices, grad_sync=True)
        opt2 = build_opt(d, iters=8, k=4, distri=True, grad_sync=False)
        opt2.failure_retry_times = 0
        assert opt2.resume()
        with pytest.raises(SchemaMismatchError) as ei:
            opt2.optimize()
        msg = str(ei.value)
        assert "grad_sync.enabled" in msg and "snapshot: True" in msg
        assert "matching grad_sync" in msg

    def test_bucket_plan_drift_fails_with_diff(self, tmp_path, devices):
        d = str(tmp_path / "ck")
        self._train_distri(d, devices, grad_sync=True,
                           grad_bucket_bytes=4 << 20)
        opt2 = build_opt(d, iters=8, k=4, distri=True, grad_sync=True,
                         grad_bucket_bytes=64 * 4)  # forces many buckets
        opt2.failure_retry_times = 0
        assert opt2.resume()
        with pytest.raises(SchemaMismatchError) as ei:
            opt2.optimize()
        msg = str(ei.value)
        assert "grad_sync.bucket_sizes" in msg
        assert "bucket plan drifted" in msg

    def test_architecture_drift_refused_at_resume(self, tmp_path):
        """A drifted model must be refused BEFORE the snapshot's params
        overwrite it (afterwards the drift would be invisible — the
        restored params ARE the old architecture); the diff names the
        mismatched leaf shapes."""
        d = str(tmp_path / "ck")
        build_opt(d, iters=4, k=4, every=2).optimize()
        opt2 = (LocalOptimizer(
            nn.Sequential().add(nn.Reshape((784,)))
            .add(nn.Linear(784, 16)).add(nn.ReLU())  # 32 → 16
            .add(nn.Linear(16, 10)).add(nn.LogSoftMax()),
            child_mod.pipeline(), nn.ClassNLLCriterion())
            .set_optim_method(optim.Adam(1e-3))
            .set_end_when(optim.max_iteration(8))
            .set_checkpoint(d, optim.several_iteration(3)))
        with pytest.raises(SchemaMismatchError) as ei:
            opt2.resume()
        msg = str(ei.value)
        assert "params" in msg and "(32, 784)" in msg \
            and "(16, 784)" in msg
        assert "architecture changed" in msg
        assert opt2.model._params is None  # model untouched

    def test_matching_schema_validates_silently(self, tmp_path):
        d = str(tmp_path / "ck")
        build_opt(d, iters=4, k=4, every=2).optimize()
        opt2 = build_opt(d, iters=8, k=4)
        assert opt2.resume()
        opt2.optimize()  # no raise
        assert opt2.state["neval"] == 8


# ================================================== shim + non-overwrite
class TestShimAndNonOverwrite:
    def test_shim_signatures_and_wire_unchanged(self, tmp_path):
        from bigdl_tpu.utils import checkpoint as ckpt
        f = ckpt.save_checkpoint(str(tmp_path / "ck"),
                                 {"w": np.arange(4, dtype=np.float32)},
                                 opt_state={"step": 3},
                                 driver_state={"neval": 3}, neval=3)
        assert f.endswith("model.3")
        blob = ckpt.load_checkpoint(f)
        assert sorted(blob) == ["driver_state", "model_state",
                                "opt_state", "params"]
        assert blob["opt_state"]["step"] == 3
        assert ckpt.latest_checkpoint(str(tmp_path / "ck")) == f

    def test_shim_latest_checkpoint_skips_corrupt(self, tmp_path):
        from bigdl_tpu.utils import checkpoint as ckpt
        d = str(tmp_path / "ck")
        f2 = ckpt.save_checkpoint(d, {"w": np.ones(64)}, neval=2)
        f4 = ckpt.save_checkpoint(d, {"w": np.ones(64)}, neval=4)
        _corrupt_array_byte(f4)
        assert ckpt.latest_checkpoint(d) == f2

    def test_versioned_non_overwrite_path_is_real(self, tmp_path):
        """The reference's unset overWriteCheckpoint: a second run into
        the same directory must refuse to clobber an existing
        model.<neval> — and over_write_checkpoint() re-allows it."""
        d = str(tmp_path / "ck")
        build_opt(d, iters=4, k=4, every=2).optimize()  # model.2/.4
        opt2 = build_opt(d, iters=4, k=4, every=2) \
            .over_write_checkpoint(False)
        with pytest.raises(FileExistsError,
                           match="overWriteCheckpoint"):
            opt2.optimize()
        opt3 = build_opt(d, iters=4, k=4, every=2) \
            .over_write_checkpoint()  # no-arg call = legacy behavior
        opt3.optimize()
        assert opt3.state["neval"] == 4

    def test_config_fields_exist(self):
        from bigdl_tpu.utils.config import Config
        c = Config()
        assert (c.checkpoint_keep_last, c.checkpoint_keep_every,
                c.checkpoint_async) == (5, 0, True)


# =============================================================== inspect
class TestCkptInspectCLI:
    def _fixture_dir(self, tmp_path):
        d = str(tmp_path / "ck")
        opt = build_opt(d, iters=4, k=4, every=2)
        opt.optimize()
        return d

    def test_ok_directory_exit_zero(self, tmp_path, capsys):
        from tools.ckpt_inspect import main
        d = self._fixture_dir(tmp_path)
        assert main([d]) == 0
        out = capsys.readouterr().out
        assert "step 4" in out and "checksum ok" in out
        assert "grad_sync off" in out
        assert f"latest valid: {os.path.join(d, 'model.4')}" in out

    def test_corrupt_snapshot_exit_one(self, tmp_path, capsys):
        from tools.ckpt_inspect import main
        d = self._fixture_dir(tmp_path)
        _corrupt_array_byte(os.path.join(d, "model.4"))
        assert main([d]) == 1
        out = capsys.readouterr().out
        assert "[corrupt]" in out
        assert f"latest valid: {os.path.join(d, 'model.2')}" in out

    def test_json_schema_and_no_verify(self, tmp_path, capsys):
        import json
        from tools.ckpt_inspect import main
        d = self._fixture_dir(tmp_path)
        assert main([d, "--json", "--no-verify"]) == 0
        rep = json.loads(capsys.readouterr().out)
        assert rep["corrupt"] == 0
        rows = rep["snapshots"]
        assert [r["step"] for r in rows] == [2, 4]
        assert all(r["checksum"] == "unverified" for r in rows)
        assert rows[0]["schema_hash"] == rows[1]["schema_hash"]
        assert rows[0]["param_leaves"] == 4

    def test_missing_path_exit_two(self, tmp_path, capsys):
        from tools.ckpt_inspect import main
        assert main([str(tmp_path / "nope")]) == 2


if __name__ == "__main__":
    sys.exit(pytest.main([__file__, "-q"]))

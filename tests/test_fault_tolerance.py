"""Fault-tolerance suite (ISSUE 5): crash-safe checkpoint layout, async
CheckpointManager, kill-and-resume bitwise recovery, loss watchdog
skip/rollback, serving health/deadline robustness.

Pinned here:
- the tracker write is atomic and torn-save debris never corrupts it;
- `load_checkpoint` scans BACKWARD past incomplete (no COMPLETE
  sentinel) and corrupt (torn meta/arrays) checkpoints to the newest
  complete one — loud warning, never a stack trace; a stale tracker
  naming a missing/torn directory falls back the same way; an
  architecture mismatch still raises (user error, not a torn save);
- the async CheckpointManager restores BITWISE-identical params/opt,
  keeps exactly one save in flight, and its keep_latest_n GC never
  deletes the protected (read/written) checkpoints;
- kill-and-resume (subprocess, SIGTERM mid-run): emergency save on the
  signal, a fresh process auto-resumes and reproduces the uninterrupted
  run's per-step losses BITWISE for >= 5 steps, and the final
  checkpoints (params + optimizer m/v) match bit for bit — data
  position, rng, params and optimizer all survived;
- the loss watchdog: NaN/inf and k-sigma spike steps are skipped
  IN-STEP (params untouched, the fp16 skip machinery driven for bf16),
  `spike_rollback_patience` consecutive bad steps reload the last
  complete checkpoint and fast-forward the data iterator, and the
  skipped/rollback counters flow through the timers-gauge path;
- GET /health speaks load-balancer: 200 while serving, 503 when the
  engine loop died poisoned or stopped; engine `deadline_s` fails the
  waiter with TimeoutError and reclaims the slot's pages;

All tier-1 (CPU, subprocesses with timeouts) except the running-request
deadline test, which needs a compiled engine step.
"""

from __future__ import annotations

import json
import math
import os
import signal
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _ft_child
from megatron_llm_tpu.config import ParallelConfig, TrainConfig, tiny_config
from megatron_llm_tpu.models import LlamaModel
from megatron_llm_tpu.optimizer import init_optimizer_state
from megatron_llm_tpu.training.checkpointing import (
    COMPLETE_FILENAME,
    TRACKER_FILENAME,
    CheckpointManager,
    checkpoint_dir,
    gc_checkpoints,
    is_checkpoint_complete,
    list_iteration_checkpoints,
    load_checkpoint,
    read_tracker,
    save_checkpoint,
)
from megatron_llm_tpu.training.watchdog import LossWatchdog

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHILD = os.path.join(REPO, "tests", "_ft_child.py")


def _tiny():
    return tiny_config(seq_length=16, max_position_embeddings=16)


def _batch(cfg, key=0, vocab_hi=None):
    hi = vocab_hi or cfg.padded_vocab_size
    tokens = jax.random.randint(jax.random.key(key), (1, 2, cfg.seq_length),
                                0, hi)
    return {"tokens": tokens, "labels": jnp.roll(tokens, -1, axis=-1)}


@pytest.fixture(scope="module")
def tiny_saved(tmp_path_factory):
    """One tiny model + three complete sync checkpoints (iters 1, 2, 3)."""
    cfg = _tiny()
    model = LlamaModel(cfg)
    params = model.init(jax.random.key(0))
    opt = init_optimizer_state(params, TrainConfig())
    d = str(tmp_path_factory.mktemp("ckpts"))
    for it in (1, 2, 3):
        save_checkpoint(d, it, params, opt, cfg,
                        consumed_train_samples=10 * it)
    return cfg, model, params, opt, d


# ---------------------------------------------------------------------------
# crash-safe layout: atomic tracker + COMPLETE sentinel
# ---------------------------------------------------------------------------


class TestCrashSafeLayout:
    def test_save_writes_sentinel_and_tracker(self, tiny_saved):
        cfg, model, params, opt, d = tiny_saved
        assert read_tracker(d) == (3, False)
        for it in (1, 2, 3):
            assert is_checkpoint_complete(checkpoint_dir(d, it))

    def test_tracker_write_is_atomic(self, tmp_path, tiny_saved):
        """No *.tmp debris survives, and stray tmp files from a crashed
        writer never confuse the reader."""
        cfg, model, params, opt, _ = tiny_saved
        d = str(tmp_path)
        save_checkpoint(d, 5, params, None, cfg)
        assert read_tracker(d) == (5, False)
        assert not [f for f in os.listdir(d) if ".tmp." in f]
        # a torn tmp from a crashed writer: reader unaffected
        with open(os.path.join(d, TRACKER_FILENAME + ".tmp.999"), "w") as f:
            f.write("99")
        assert read_tracker(d) == (5, False)

    def test_list_iteration_checkpoints_newest_first(self, tiny_saved):
        _, _, _, _, d = tiny_saved
        assert [it for it, _ in list_iteration_checkpoints(d)] == [3, 2, 1]


# ---------------------------------------------------------------------------
# backward-scan recovery (satellites 1+2 + tentpole crash-safe load)
# ---------------------------------------------------------------------------


class TestTornSaveRecovery:
    @pytest.fixture()
    def saved(self, tmp_path, tiny_saved):
        """Fresh 3-checkpoint dir per test (tests corrupt it)."""
        cfg, model, params, opt, _ = tiny_saved
        d = str(tmp_path / "ck")
        for it in (1, 2, 3):
            save_checkpoint(d, it, params, opt, cfg,
                            consumed_train_samples=10 * it)
        return cfg, params, opt, d

    def test_missing_sentinel_falls_back(self, saved, capsys):
        cfg, params, opt, d = saved
        os.remove(os.path.join(checkpoint_dir(d, 3), COMPLETE_FILENAME))
        out = load_checkpoint(d, params, opt, cfg)
        assert out is not None and out[3] == 2
        cap = capsys.readouterr().out
        assert "skipping incomplete checkpoint" in cap
        assert "OLDER checkpoint" in cap

    def test_torn_meta_falls_back(self, saved, capsys):
        """COMPLETE present but meta.json gone (satellite 2's
        FileNotFoundError case): warn + fall back, never a traceback."""
        cfg, params, opt, d = saved
        os.remove(os.path.join(checkpoint_dir(d, 3), "meta.json"))
        out = load_checkpoint(d, params, opt, cfg)
        assert out is not None and out[3] == 2
        assert out[2]["consumed_train_samples"] == 20
        assert "unreadable" in capsys.readouterr().out

    def test_torn_arrays_fall_back(self, saved, capsys):
        """Truncated tensorstore data (a preemption mid-write behind a
        lying COMPLETE, e.g. lost page cache): still recovers."""
        cfg, params, opt, d = saved
        model_dir = os.path.join(checkpoint_dir(d, 3), "model")
        nuked = 0
        for root, _, files in os.walk(model_dir):
            for f in files:
                p = os.path.join(root, f)
                if os.path.getsize(p) > 0:
                    with open(p, "w") as fh:
                        fh.truncate(0)
                    nuked += 1
        assert nuked > 0
        out = load_checkpoint(d, params, opt, cfg)
        assert out is not None and out[3] == 2
        assert "unreadable" in capsys.readouterr().out

    def test_stale_tracker_does_not_hide_newer_complete(self, saved,
                                                        capsys):
        """A crash between the COMPLETE sentinel and the tracker write
        leaves the tracker one save behind; resume must take the newer
        CERTIFIED checkpoint, not silently discard it."""
        cfg, params, opt, d = saved
        with open(os.path.join(d, TRACKER_FILENAME), "w") as f:
            f.write("2")  # stale: iter 3 is complete but unreferenced
        out = load_checkpoint(d, params, opt, cfg)
        assert out is not None and out[3] == 3
        assert "OLDER" not in capsys.readouterr().out

    def test_tracker_names_missing_dir(self, saved, capsys):
        """Stale tracker pointing at a GC'd/torn directory: the scan
        resumes from the newest real checkpoint instead of crashing."""
        cfg, params, opt, d = saved
        with open(os.path.join(d, TRACKER_FILENAME), "w") as f:
            f.write("99")
        out = load_checkpoint(d, params, opt, cfg)
        assert out is not None and out[3] == 3

    def test_all_torn_returns_none_with_warning(self, saved, capsys):
        cfg, params, opt, d = saved
        for it in (1, 2, 3):
            os.remove(os.path.join(checkpoint_dir(d, it), "meta.json"))
        assert load_checkpoint(d, params, opt, cfg) is None
        assert "starting from scratch" in capsys.readouterr().out

    def test_arch_mismatch_still_raises(self, saved):
        """A wrong --num_layers is a user error, not a torn save — the
        backward scan must NOT paper over it."""
        cfg, params, opt, d = saved
        bad = tiny_config(num_layers=3, seq_length=16,
                          max_position_embeddings=16)
        with pytest.raises(ValueError, match="num_layers"):
            load_checkpoint(d, params, opt, bad)

    def test_explicit_iteration_is_exempt_from_scan(self, saved):
        cfg, params, opt, d = saved
        os.remove(os.path.join(checkpoint_dir(d, 2), "meta.json"))
        with pytest.raises(FileNotFoundError):
            load_checkpoint(d, params, opt, cfg, iteration=2)


# ---------------------------------------------------------------------------
# async CheckpointManager
# ---------------------------------------------------------------------------


class TestCheckpointManager:
    def test_async_save_restores_bitwise(self, tmp_path, tiny_saved):
        cfg, model, params, opt, _ = tiny_saved
        d = str(tmp_path / "async")
        mgr = CheckpointManager(d)
        mgr.save(7, params, opt, cfg, consumed_train_samples=42)
        assert mgr.saves == 1 and mgr.last_blocked_ms >= 0.0
        mgr.wait_until_finished()
        assert is_checkpoint_complete(checkpoint_dir(d, 7))
        assert read_tracker(d) == (7, False)
        p2, o2, meta, it = load_checkpoint(d, params, opt, cfg)
        assert it == 7 and meta["consumed_train_samples"] == 42
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(p2)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(opt.m), jax.tree.leaves(o2.m)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert int(o2.step) == int(opt.step)

    def test_single_inflight_back_to_back(self, tmp_path, tiny_saved):
        """A new save waits on the previous finalizer — both end up
        certified, the tracker lands on the newest."""
        cfg, model, params, opt, _ = tiny_saved
        d = str(tmp_path / "seq")
        mgr = CheckpointManager(d)
        mgr.save(1, params, opt, cfg)
        mgr.save(2, params, opt, cfg)  # blocks until save 1 certified
        assert is_checkpoint_complete(checkpoint_dir(d, 1))
        mgr.wait_until_finished()
        assert is_checkpoint_complete(checkpoint_dir(d, 2))
        assert read_tracker(d) == (2, False)

    def test_manager_gc_keep_latest_n(self, tmp_path, tiny_saved):
        cfg, model, params, opt, _ = tiny_saved
        d = str(tmp_path / "gc")
        mgr = CheckpointManager(d, keep_latest_n=2)
        for it in (1, 2, 3, 4):
            mgr.save(it, params, None, cfg)
        mgr.wait_until_finished()
        assert [it for it, _ in list_iteration_checkpoints(d)] == [4, 3]
        assert read_tracker(d) == (4, False)

    def test_manager_gc_protects_read_checkpoint(self, tmp_path,
                                                 tiny_saved):
        cfg, model, params, opt, _ = tiny_saved
        d = str(tmp_path / "prot")
        mgr = CheckpointManager(d, keep_latest_n=1)
        mgr.protect(checkpoint_dir(d, 1))  # "resume read this one"
        for it in (1, 2, 3):
            mgr.save(it, params, None, cfg)
        mgr.wait_until_finished()
        assert [it for it, _ in list_iteration_checkpoints(d)] == [3, 1]

    def test_sync_mode_still_crash_safe(self, tmp_path, tiny_saved):
        cfg, model, params, opt, _ = tiny_saved
        d = str(tmp_path / "sync")
        mgr = CheckpointManager(d, async_save=False)
        mgr.save(9, params, opt, cfg)
        # no background work: certified the moment save() returns
        assert is_checkpoint_complete(checkpoint_dir(d, 9))
        assert read_tracker(d) == (9, False)

    def test_sync_mode_runs_retention_gc(self, tmp_path, tiny_saved):
        """--no_async_save must not silently disable --keep_latest_n."""
        cfg, model, params, opt, _ = tiny_saved
        d = str(tmp_path / "syncgc")
        mgr = CheckpointManager(d, keep_latest_n=2, async_save=False)
        for it in (1, 2, 3, 4):
            mgr.save(it, params, None, cfg)
        assert [it for it, _ in list_iteration_checkpoints(d)] == [4, 3]


def test_gc_semantics(tmp_path, tiny_saved):
    cfg, model, params, opt, _ = tiny_saved
    d = str(tmp_path / "g")
    for it in (1, 2, 3, 4):
        save_checkpoint(d, it, params, None, cfg)
    # an incomplete dir NEWER than the horizon (an in-flight save from
    # another writer) must survive
    os.makedirs(checkpoint_dir(d, 5))
    deleted = gc_checkpoints(d, 2, protect=[checkpoint_dir(d, 1)])
    assert sorted(deleted) == [checkpoint_dir(d, 2)]
    left = {it for it, _ in list_iteration_checkpoints(d)}
    assert left == {1, 3, 4, 5}


# ---------------------------------------------------------------------------
# loss watchdog
# ---------------------------------------------------------------------------


class TestLossWatchdog:
    def test_threshold_inf_until_history(self):
        wd = LossWatchdog(k_sigma=3.0, window=16, min_history=4)
        for i in range(3):
            assert wd.threshold() == math.inf
            assert not wd.observe(5.0 + 0.01 * i)
        assert wd.threshold() == math.inf  # 3 < min_history
        wd.observe(5.0)
        assert wd.threshold() < math.inf

    def test_spike_and_nan_detection(self):
        wd = LossWatchdog(k_sigma=3.0, window=16, patience=2,
                          min_history=4)
        for i in range(8):
            assert not wd.observe(5.0 + 0.01 * (i % 3))
        assert wd.observe(50.0)  # spike
        assert wd.skipped == 1 and wd.consecutive_bad == 1
        assert not wd.should_rollback()
        assert wd.observe(float("nan"))  # nan always bad
        assert wd.should_rollback()
        wd.note_rollback()
        assert wd.rollbacks == 1 and wd.consecutive_bad == 0
        assert wd.threshold() == math.inf  # window cleared
        assert wd.counters() == {"loss_watchdog_skipped": 2,
                                 "loss_watchdog_rollbacks": 1}

    def test_good_step_resets_streak(self):
        wd = LossWatchdog(k_sigma=3.0, window=16, patience=3,
                          min_history=4)
        for _ in range(6):
            wd.observe(2.0)
        wd.observe(float("inf"))
        wd.observe(float("inf"))
        wd.observe(2.0)
        assert wd.consecutive_bad == 0 and wd.skipped == 2

    def test_disabled_spike_detection_still_blocks_nan(self):
        wd = LossWatchdog()  # ksigma 0, patience 0
        for _ in range(20):
            assert not wd.observe(3.0)
        assert wd.threshold() == math.inf
        assert wd.observe(float("nan"))
        assert not wd.should_rollback()

    def test_small_window_still_arms_threshold(self):
        """window < default min_history must still detect spikes (the
        accepted-but-dead-config regression)."""
        wd = LossWatchdog(k_sigma=3.0, window=4)
        for i in range(4):
            wd.observe(5.0 + 0.01 * i)
        assert wd.threshold() < math.inf
        assert wd.observe(50.0)


class _PoisonLossModel:
    """Hooked loss: any microbatch whose tokens[0, 0] == magic gets
    `inject` added to the loss (NaN or a spike) — the ISSUE-5 test hook
    for driving the in-step skip gate with real data flow."""

    def __init__(self, inner, magic=255, inject=float("nan")):
        self._inner = inner
        self._magic = magic
        self._inject = inject

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def loss(self, params, **kw):
        base = self._inner.loss(params, **kw)
        poison = kw["tokens"][0, 0] == self._magic
        return base + jnp.where(poison, jnp.float32(self._inject),
                                jnp.float32(0.0))


class TestInStepSkip:
    """The spike-threshold gate inside make_train_step: a bad step
    leaves params/optimizer bitwise untouched (the fp16 skip machinery,
    driven for bf16)."""

    def test_spike_threshold_skips_update(self):
        from megatron_llm_tpu.training.train_step import make_train_step

        cfg = _tiny()
        model = LlamaModel(cfg)
        params = model.init(jax.random.key(0))
        tcfg = TrainConfig(micro_batch_size=2, global_batch_size=2, lr=1e-3)
        opt = init_optimizer_state(params, tcfg)
        step = jax.jit(make_train_step(model, tcfg,
                                       ParallelConfig(num_microbatches=1)))
        batch = _batch(cfg)
        lr, wd = jnp.float32(1e-3), jnp.float32(0.0)
        # threshold above the loss: normal update
        p1, s1, st1 = step(params, opt, batch, lr, wd, None,
                           jnp.float32(np.inf))
        assert int(st1["skipped"]) == 0
        assert not np.allclose(np.asarray(jax.tree.leaves(p1)[0]),
                               np.asarray(jax.tree.leaves(params)[0]))
        # threshold below the loss: the whole update is skipped
        thr = jnp.float32(float(st1["loss"]) - 1.0)
        p2, s2, st2 = step(params, opt, batch, lr, wd, None, thr)
        assert int(st2["skipped"]) == 1
        for a, b in zip(jax.tree.leaves(p2), jax.tree.leaves(params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(s2.m), jax.tree.leaves(opt.m)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert int(s2.step) == int(opt.step)

    def test_spike_skip_never_drives_fp16_scale(self):
        """A finite-gradient watchdog skip must leave the fp16 loss
        scale and hysteresis untouched — only GENUINE overflow
        (non-finite grads) backs the scale off."""
        from megatron_llm_tpu.optimizer.optimizer import (
            get_grad_scaler,
            optimizer_step,
        )

        cfg = _tiny()
        model = LlamaModel(cfg)
        params = model.init(jax.random.key(0))
        tcfg = TrainConfig(micro_batch_size=2, global_batch_size=2,
                           lr=1e-3, fp16=True, bf16=False,
                           initial_loss_scale=2.0**10, hysteresis=1)
        opt = init_optimizer_state(params, tcfg)
        scaler = get_grad_scaler(tcfg)
        grads = jax.tree.map(
            lambda p: jnp.ones(p.shape, jnp.float32), params)
        p1, s1, st1 = optimizer_step(
            params, grads, opt, tcfg, jnp.float32(1e-3),
            found_inf=jnp.bool_(True), scaler=scaler)
        assert int(st1["skipped"]) == 1  # update skipped...
        assert float(s1.scaler["scale"]) == 2.0**10  # ...scale intact
        assert int(s1.scaler["hysteresis_tracker"]) == 1
        for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_nan_loss_skips_with_inf_threshold(self):
        from megatron_llm_tpu.training.train_step import make_train_step

        cfg = _tiny()
        model = _PoisonLossModel(LlamaModel(cfg), inject=float("nan"))
        params = model.init(jax.random.key(0))
        tcfg = TrainConfig(micro_batch_size=2, global_batch_size=2, lr=1e-3)
        opt = init_optimizer_state(params, tcfg)
        step = jax.jit(make_train_step(model, tcfg,
                                       ParallelConfig(num_microbatches=1)))
        batch = _batch(cfg)
        batch["tokens"] = batch["tokens"].at[0, 0, 0].set(255)  # poison
        p1, s1, st1 = step(params, opt, batch, jnp.float32(1e-3),
                           jnp.float32(0.0), None, jnp.float32(np.inf))
        assert int(st1["skipped"]) == 1
        for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_watchdog_rollback_end_to_end(tmp_path):
    """NaN-injection through a hooked loss (ISSUE-5 satellite): good
    steps -> checkpoint -> a run of poisoned batches -> in-step skips ->
    patience exhausted -> ROLLBACK to the last complete checkpoint ->
    the data iterator keeps going (fast-forward past the poison window)
    -> training completes with finite params and the counters on the
    gauge channel."""
    from megatron_llm_tpu.training.trainer import Trainer

    cfg = _tiny()
    model = _PoisonLossModel(LlamaModel(cfg), inject=float("nan"))
    save_dir = str(tmp_path / "ck")
    tcfg = TrainConfig(
        micro_batch_size=2, global_batch_size=2, lr=1e-3,
        train_iters=18, log_interval=1, eval_interval=0,
        save=save_dir, save_interval=5,
        spike_rollback_patience=2,
    )
    rng = np.random.RandomState(0)
    batches = []
    for i in range(30):
        # vocab capped at 200 so a normal batch can never trip the magic
        t = rng.randint(0, 200, size=(1, 2, cfg.seq_length + 1))
        if i in (10, 11):  # iterations 11 + 12 are poisoned
            t[0, 0, 0] = 255
        batches.append(t.astype(np.int32))

    trainer = Trainer(model, tcfg, ParallelConfig(num_microbatches=1),
                      train_data_iterator=batches)
    state = trainer.setup()
    state = trainer.train(state)

    assert trainer.watchdog.skipped == 2
    assert trainer.watchdog.rollbacks == 1
    # rolled back to iteration 10, then trained through to the end
    assert state.iteration == 18
    for leaf in jax.tree.leaves(state.params):
        assert np.isfinite(np.asarray(leaf)).all()
    gauges = trainer.timers.gauges()
    assert gauges.get("loss_watchdog_skipped") == 2
    assert gauges.get("loss_watchdog_rollbacks") == 1
    assert "ckpt_blocked_ms" in gauges
    # neither the data iterator nor the consumed counter was rewound
    # (the counter IS the data position a later resume restarts from):
    # 20 batches consumed = 10 good + 2 poison-skipped + 8 post-rollback
    assert state.consumed_train_samples == 20 * 2

    # flight-recorder rollback artifact (ISSUE 13): the rollback left a
    # JSON record in the save dir whose verdict trail names the exact
    # failing steps and the restored iteration — loadable + correlated
    # by step id, not a log tail
    import glob

    arts = glob.glob(os.path.join(
        save_dir, "flight_record_watchdog-rollback_*.json"))
    assert arts, sorted(os.listdir(save_dir))
    with open(arts[0]) as f:
        rec = json.load(f)
    assert rec["reason"] == "watchdog-rollback"
    assert rec["extra"]["restored_step"] == 10
    assert rec["extra"]["poison_window"] == 2
    bad = [e for e in rec["events"] if e["kind"] == "watchdog_bad"]
    assert [e["step"] for e in bad] == [11, 12], bad
    assert any(e["kind"] == "watchdog_rollback"
               and e["restored_step"] == 10 for e in rec["events"])
    # the per-step trail brackets the poison window
    rec_steps = [e["step"] for e in rec["events"] if e["kind"] == "step"]
    assert 10 in rec_steps and 11 in rec_steps and 12 in rec_steps


def test_rollback_with_no_save_optim(tmp_path, capsys):
    """--no_save_optim checkpoints have no optim dir; rollback must
    restore params-only instead of misreading every healthy checkpoint
    as torn."""
    from megatron_llm_tpu.training.trainer import Trainer, TrainState

    cfg = _tiny()
    model = LlamaModel(cfg)
    save_dir = str(tmp_path / "ck")
    tcfg = TrainConfig(micro_batch_size=2, global_batch_size=2, lr=1e-3,
                       no_save_optim=True, save=save_dir,
                       spike_rollback_patience=1)
    trainer = Trainer(model, tcfg, ParallelConfig(num_microbatches=1))
    params = model.init(jax.random.key(0))
    opt = init_optimizer_state(params, tcfg)
    state = TrainState(params=params, opt_state=opt, iteration=7,
                       consumed_train_samples=14)
    trainer._save(state, blocking=True)
    state.iteration = 9
    assert trainer._rollback(state) is True
    assert state.iteration == 7
    assert state.opt_state is opt  # params-only restore kept the live opt
    assert "unreadable" not in capsys.readouterr().out


def test_rollback_without_save_dir_is_skip_only(capsys):
    from megatron_llm_tpu.training.trainer import Trainer

    cfg = _tiny()
    model = _PoisonLossModel(LlamaModel(cfg), inject=float("nan"))
    tcfg = TrainConfig(micro_batch_size=2, global_batch_size=2, lr=1e-3,
                       train_iters=6, log_interval=100, eval_interval=0,
                       spike_rollback_patience=2)
    rng = np.random.RandomState(0)
    batches = []
    for i in range(10):
        t = rng.randint(0, 200, size=(1, 2, cfg.seq_length + 1))
        if i in (2, 3, 4):
            t[0, 0, 0] = 255
        batches.append(t.astype(np.int32))
    trainer = Trainer(model, tcfg, ParallelConfig(num_microbatches=1),
                      train_data_iterator=batches)
    state = trainer.train(trainer.setup())
    assert trainer.watchdog.rollbacks == 0
    assert trainer.watchdog.skipped == 3
    assert state.iteration == 6
    assert "skip-only" in capsys.readouterr().out


# ---------------------------------------------------------------------------
# kill-and-resume (subprocess crash injection)
# ---------------------------------------------------------------------------


def _child_env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    return env


def _read_losses(workdir):
    path = os.path.join(workdir, "losses.txt")
    if not os.path.exists(path):
        return {}
    out = {}
    with open(path) as f:
        for line in f:
            parts = line.split()
            if len(parts) == 3 and parts[0] == "STEP":
                out[int(parts[1])] = parts[2]
    return out


def test_kill_and_resume_bitwise(tmp_path):
    """SIGTERM a subprocess trainer mid-run: emergency save, clean exit;
    a fresh process resumes and reproduces the uninterrupted run's loss
    trajectory BITWISE for >= 5 steps; the final checkpoints (params +
    optimizer moments) are bit-identical."""
    n_iters = _ft_child.TRAIN_ITERS
    ref_dir = str(tmp_path / "ref")
    kill_dir = str(tmp_path / "kill")
    os.makedirs(ref_dir)
    os.makedirs(kill_dir)

    # 1) uninterrupted reference
    r = subprocess.run(
        [sys.executable, CHILD, ref_dir], env=_child_env(), cwd=REPO,
        capture_output=True, text=True, timeout=240)
    assert r.returncode == 0, r.stdout + r.stderr
    ref_losses = _read_losses(ref_dir)
    assert sorted(ref_losses) == list(range(1, n_iters + 1))

    # 2) same run, SIGTERM'd once a few steps are on disk
    proc = subprocess.Popen(
        [sys.executable, CHILD, kill_dir, "--step_delay", "0.3"],
        env=_child_env(), cwd=REPO, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True)
    try:
        deadline = time.time() + 180
        while time.time() < deadline:
            if len(_read_losses(kill_dir)) >= 3:
                break
            assert proc.poll() is None, \
                "child died before the kill: " + proc.stdout.read()
            time.sleep(0.05)
        else:
            pytest.fail("child never produced 3 steps")
        proc.send_signal(signal.SIGTERM)
        out, _ = proc.communicate(timeout=180)
    finally:
        if proc.poll() is None:
            proc.kill()
    assert proc.returncode == 0, out
    assert "emergency save" in out
    k = max(_read_losses(kill_dir))
    assert k < n_iters, "child finished before the kill landed"
    assert k <= n_iters - 5, f"kill landed too late (step {k}) for a " \
        f"5-step overlap; raise TRAIN_ITERS"
    # the emergency save certified a checkpoint at the killed iteration
    assert read_tracker(os.path.join(kill_dir, "ckpt")) == (k, False)

    # flight-recorder artifact (ISSUE 13): the killed run left a
    # readable last-N-steps record that correlates to the emergency-
    # saved iteration by step id — the postmortem starts from this
    # JSON, not a log tail
    import glob

    arts = glob.glob(os.path.join(kill_dir, "ckpt",
                                  "flight_record_sigterm_*.json"))
    assert arts, sorted(os.listdir(os.path.join(kill_dir, "ckpt")))
    with open(arts[0]) as f:
        rec = json.load(f)
    assert rec["reason"] == "sigterm"
    assert rec["extra"]["step"] == k
    rec_steps = [e for e in rec["events"] if e["kind"] == "step"]
    assert rec_steps, rec["events"]
    assert rec_steps[-1]["step"] == k
    # the recorded per-step losses match the on-disk loss log for the
    # overlapping steps (the record is the run, not a reconstruction)
    kill_losses = _read_losses(kill_dir)
    for e in rec_steps:
        assert float.hex(e["loss"]) == kill_losses[e["step"]], e
    assert any(e["kind"] == "sigterm" for e in rec["events"])
    assert any(e["kind"] == "ckpt_certified" and e["step"] == k
               for e in rec["events"])

    # 3) fresh process auto-resumes from the emergency save
    r2 = subprocess.run(
        [sys.executable, CHILD, kill_dir], env=_child_env(), cwd=REPO,
        capture_output=True, text=True, timeout=240)
    assert r2.returncode == 0, r2.stdout + r2.stderr
    assert f"DONE iter={n_iters}" in r2.stdout

    resumed = _read_losses(kill_dir)
    assert sorted(resumed) == list(range(1, n_iters + 1))
    overlap = [s for s in range(k + 1, n_iters + 1)]
    assert len(overlap) >= 5
    for s in overlap:
        assert resumed[s] == ref_losses[s], (
            f"loss at step {s} diverged after resume: "
            f"{resumed[s]} != {ref_losses[s]}")

    # 4) final checkpoints bitwise: params AND optimizer moments
    # (concrete templates: orbax needs shardings to restore into)
    cfg = _ft_child.make_child_cfg()
    model = LlamaModel(cfg)
    tmpl = model.init(jax.random.key(0))
    tcfg = _ft_child.make_child_tcfg("unused")
    opt_tmpl = init_optimizer_state(tmpl, tcfg)
    ref_ck = load_checkpoint(os.path.join(ref_dir, "ckpt"), tmpl,
                             opt_tmpl, cfg)
    res_ck = load_checkpoint(os.path.join(kill_dir, "ckpt"), tmpl,
                             opt_tmpl, cfg)
    assert ref_ck[3] == res_ck[3] == n_iters
    assert ref_ck[2]["consumed_train_samples"] == \
        res_ck[2]["consumed_train_samples"]
    for a, b in zip(jax.tree.leaves(ref_ck[0]), jax.tree.leaves(res_ck[0])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for tree_a, tree_b in ((ref_ck[1].m, res_ck[1].m),
                           (ref_ck[1].v, res_ck[1].v)):
        for a, b in zip(jax.tree.leaves(tree_a), jax.tree.leaves(tree_b)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# serving robustness: /health + deadline_s
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def serve_model():
    cfg = tiny_config(compute_dtype=jnp.float32, use_decode_attn=False)
    model = LlamaModel(cfg)
    params = model.init(jax.random.key(7))
    return model, params


def _engine(model, params, **over):
    from megatron_llm_tpu.inference.engine import DecodeEngine

    kw = dict(slots=2, page_size=16, max_context=64, max_queue=8,
              termination_id=None, vocab_size=256)
    kw.update(over)
    return DecodeEngine(model, params, **kw)


class _Tok:
    """Minimal tokenizer for the HTTP fixtures."""
    eod = 0
    bos = 1

    def tokenize(self, s):
        return [min(ord(c), 255) for c in s]

    def detokenize(self, ids):
        return "".join(chr(min(i, 127)) for i in ids)


def _serve(model, params, engine):
    import socket

    from megatron_llm_tpu.inference.server import MegatronServer

    srv = MegatronServer(model, params, _Tok(), engine=engine)
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    httpd = srv.run(host="127.0.0.1", port=port, block=False)
    return srv, httpd, port


def _get(port, path):
    import urllib.error
    import urllib.request

    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}{path}", timeout=10) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


class TestHealthEndpoint:
    def test_engineless_server_is_ok(self, serve_model):
        model, params = serve_model
        srv, httpd, port = _serve(model, params, engine=None)
        try:
            status, body = _get(port, "/health")
            assert status == 200 and body == {"status": "ok",
                                              "engine": None}
        finally:
            srv.stop()

    def test_engine_health_transitions(self, serve_model):
        """Running: 200 with the liveness snapshot. Poisoned serve loop:
        503 with the fatal error. Stopped: 503."""
        model, params = serve_model
        eng = _engine(model, params)
        srv, httpd, port = _serve(model, params, eng)
        try:
            status, body = _get(port, "/health")
            assert status == 200 and body["status"] == "ok"
            assert body["engine"]["alive"] is True
            assert body["engine"]["broken"] is None
            assert body["engine"]["queue_depth"] == 0
            # poison the loop the way a fatal step error does
            eng._broken = "engine step failed: XlaRuntimeError('boom')"
            status, body = _get(port, "/health")
            assert status == 503 and body["status"] == "unhealthy"
            assert "boom" in body["engine"]["broken"]
            eng._broken = None
            eng.stop(drain=True)
            status, body = _get(port, "/health")
            assert status == 503 and body["engine"]["alive"] is False
        finally:
            if httpd is not None:
                httpd.shutdown()


class TestDeadline:
    def test_queued_deadline_times_out_without_device_work(self,
                                                           serve_model):
        """A request that expires while still queued fails its waiter
        with TimeoutError on the next scheduler round — no slots, no
        pages, no compilation involved."""
        model, params = serve_model
        eng = _engine(model, params)
        req = eng.submit([1, 2, 3], 8, deadline_s=0.01)
        time.sleep(0.03)
        eng._expire_deadlines()
        with pytest.raises(TimeoutError, match="deadline_s"):
            req.result(timeout=1.0)
        assert eng.counters()["serve_timed_out"] == 1
        assert len(eng._queue) == 0

    def test_submit_rejects_nonpositive_deadline(self, serve_model):
        model, params = serve_model
        eng = _engine(model, params)
        with pytest.raises(ValueError, match="deadline_s"):
            eng.submit([1, 2, 3], 8, deadline_s=0.0)

    @pytest.mark.slow
    def test_running_deadline_retires_slot_and_frees_pages(self,
                                                           serve_model):
        """An in-flight request past its deadline fails with
        TimeoutError, its pages return to the pool, and the engine keeps
        serving new requests."""
        from conftest import kernel_interpret_mode  # noqa: F401

        model, params = serve_model
        eng = _engine(model, params, step_horizon=1,
                      prefill_chunk_tokens=0)
        total_pages = eng.num_pages - 1
        req = eng.submit([1, 2, 3, 4], 48, deadline_s=0.15)
        # drive the scheduler on this thread: prefill + decode rounds
        # until the deadline fires (CPU rounds are slow enough that the
        # budget expires long before 48 tokens land)
        deadline = time.time() + 120
        while not req.done.is_set() and time.time() < deadline:
            eng.step()
        with pytest.raises(TimeoutError, match="pages reclaimed"):
            req.result(timeout=1.0)
        assert len(eng._free_pages) == total_pages
        assert all(s.req is None for s in eng._slots)
        # the engine is still healthy: a fresh request completes
        req2 = eng.submit([1, 2, 3, 4], 4)
        while not req2.done.is_set():
            eng.step()
        toks, _ = req2.result(timeout=1.0)
        assert len(toks) == 8


# ---------------------------------------------------------------------------
# ZeRO-1 dp-sharded optimizer state: bitwise save/resume (ISSUE 10)
# ---------------------------------------------------------------------------


def test_zero1_sharded_state_bitwise_resume(tmp_path):
    """Train under the explicit ZeRO-1 path (dp2), save mid-run, resume
    a FRESH trainer from the checkpoint: per-step losses after resume
    and final params + dp-sharded m/v are BITWISE the uninterrupted
    run's — the distributed-optimizer tree round-trips through the
    checkpoint (tensorstore writes global arrays; restore reshards into
    the live zero1 templates)."""
    import dataclasses

    from megatron_llm_tpu.parallel.mesh import (
        destroy_parallel,
        initialize_parallel,
    )
    from megatron_llm_tpu.training.trainer import Trainer

    cfg = tiny_config(seq_length=32, max_position_embeddings=32,
                      compute_dtype=jnp.float32, params_dtype=jnp.float32)
    dp, num_micro, mbs = 2, 1, 2
    rows = mbs * dp
    base_t = TrainConfig(micro_batch_size=mbs, global_batch_size=rows,
                         lr=1e-3, train_iters=4)
    pcfg = ParallelConfig(data_parallel_size=dp,
                          num_microbatches=num_micro,
                          use_distributed_optimizer=True)

    def batches(n):
        rs = np.random.RandomState(42)
        return [rs.randint(0, cfg.padded_vocab_size,
                           (num_micro, rows, cfg.seq_length + 1))
                .astype(np.int32) for _ in range(n)]

    def run(tcfg, n_steps, state=None, trainer=None):
        trainer = trainer or Trainer(LlamaModel(cfg), tcfg, pcfg)
        state = state or trainer.setup()
        losses = []
        for text in batches(4)[state.iteration:state.iteration + n_steps]:
            losses.append(float(trainer.train_step(state, text)["loss"]))
        return trainer, state, losses

    ctx = initialize_parallel(dp=dp, pp=1, tp=1)
    try:
        # uninterrupted 4 steps
        _, ref_state, ref_losses = run(base_t, 4)
        ref_p = jax.tree.map(np.asarray, ref_state.params)
        ref_m = jax.tree.map(np.asarray, ref_state.opt_state.m)

        # 2 steps -> blocking save -> fresh trainer resumes 2 more
        save_t = dataclasses.replace(base_t, save=str(tmp_path))
        tr1, st1, first = run(save_t, 2)
        tr1._save(st1, blocking=True)
        load_t = dataclasses.replace(base_t, save=str(tmp_path),
                                     load=str(tmp_path))
        tr2 = Trainer(LlamaModel(cfg), load_t, pcfg)
        st2 = tr2.setup()
        assert st2.iteration == 2
        # the restored m/v carry the zero1 templates' dp-sharding (the
        # spec string may normalize differently — compare the physical
        # per-device shard shape)
        tpl = jax.tree.leaves(st1.opt_state.m)[0]
        got = jax.tree.leaves(st2.opt_state.m)[0]
        assert got.sharding.shard_shape(got.shape) \
            == tpl.sharding.shard_shape(tpl.shape)
        assert got.sharding.shard_shape(got.shape) != got.shape  # sharded
        _, st2, rest = run(load_t, 2, state=st2, trainer=tr2)

        assert first + rest == ref_losses, (first, rest, ref_losses)
        for a, b in zip(jax.tree.leaves(ref_p),
                        jax.tree.leaves(
                            jax.tree.map(np.asarray, st2.params))):
            np.testing.assert_array_equal(a, b)
        for a, b in zip(jax.tree.leaves(ref_m),
                        jax.tree.leaves(
                            jax.tree.map(np.asarray, st2.opt_state.m))):
            np.testing.assert_array_equal(a, b)
    finally:
        destroy_parallel()

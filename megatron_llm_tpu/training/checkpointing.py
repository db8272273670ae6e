"""Checkpoint save/load — orbax/tensorstore, mesh-shape independent.

Parity target: ref megatron/checkpointing.py — iteration-numbered
directories, a `latest_checkpointed_iteration.txt` tracker (:170),
`--finetune` semantics (reset iteration, skip optim/rng, :583-625),
arg cross-checking (:35-66), rng state for bitwise resume (:217-240).

TPU-first differences: one orbax checkpoint holds the whole (sharded)
params/optimizer tree keyed by logical names — tensorstore reshards on load
under any mesh shape, which deletes the entire reason the reference needs
tools/checkpoint_util.py's tp/pp re-partitioner (SURVEY.md §5). Layout:

    <save>/iter_0000100/{model,optim,meta.json,COMPLETE}
    <save>/latest_checkpointed_iteration.txt

Fault tolerance (ISSUE 5):
- the tracker is written ATOMICALLY (tmp in the same directory + fsync +
  os.rename) — a crash mid-write can never corrupt it;
- every checkpoint directory carries a `COMPLETE` sentinel, written LAST
  (after the orbax commits and meta.json land), so a torn save is
  distinguishable from a finished one without trusting mtimes;
- `load_checkpoint` scans BACKWARD past incomplete/corrupt iteration
  directories to the newest complete one — a preempted pod resumes from
  the last good save with a loud warning, never a stack trace;
- `CheckpointManager` is the ASYNC save path: `save()` returns to the
  train loop right after the device→host copy (orbax async), a single
  save is in flight at a time (a new save waits on the previous), the
  sentinel/tracker/retention-GC finalization runs on a background
  thread, and `wait_until_finished()` is only required at exit. The
  blocking portion of each save is surfaced as the `ckpt_blocked_ms`
  timers gauge.
- `--keep_latest_n` retention GC deletes old iteration directories but
  never the one currently being written or the one a resume read.
"""

from __future__ import annotations

import dataclasses
import json
import os
import re
import shutil
import threading
import time
from typing import Any, Iterable, List, Optional, Tuple

import jax
import numpy as np
import orbax.checkpoint as ocp

TRACKER_FILENAME = "latest_checkpointed_iteration.txt"
COMPLETE_FILENAME = "COMPLETE"
_ITER_DIR_RE = re.compile(r"^iter_(\d{7})$")


def checkpoint_dir(save_dir: str, iteration: int, release: bool = False) -> str:
    """ref: get_checkpoint_name (checkpointing.py:77-96) directory level."""
    name = "release" if release else f"iter_{iteration:07d}"
    return os.path.join(save_dir, name)


def read_tracker(load_dir: str) -> Tuple[Optional[int], bool]:
    """ref: read_metadata (checkpointing.py:160-216)."""
    path = os.path.join(load_dir, TRACKER_FILENAME)
    if not os.path.isfile(path):
        return None, False
    with open(path) as f:
        raw = f.read().strip()
    if raw == "release":
        return None, True
    return int(raw), False


def _atomic_write(path: str, data: str) -> None:
    """tmp in the SAME directory + fsync + rename: the write is all-or-
    nothing on every POSIX filesystem (rename within a directory is
    atomic; the fsync orders the data before the name swap)."""
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        f.write(data)
        f.flush()
        os.fsync(f.fileno())
    os.rename(tmp, path)


def _write_tracker(save_dir: str, iteration: int, release: bool = False) -> None:
    """Crash-safe tracker update: a SIGKILL between any two instructions
    leaves either the old tracker or the new one, never a torn file."""
    _atomic_write(os.path.join(save_dir, TRACKER_FILENAME),
                  "release" if release else str(iteration))


def _mark_complete(path: str) -> None:
    """The per-checkpoint COMPLETE sentinel — written LAST, so its
    presence certifies every artifact (model/optim/meta.json) landed."""
    _atomic_write(os.path.join(path, COMPLETE_FILENAME), "1")


def is_checkpoint_complete(path: str) -> bool:
    return os.path.isfile(os.path.join(path, COMPLETE_FILENAME))


def list_iteration_checkpoints(load_dir: str) -> List[Tuple[int, str]]:
    """(iteration, path) for every iter_* directory, newest first."""
    out = []
    try:
        names = os.listdir(load_dir)
    except OSError:
        return []
    for name in names:
        m = _ITER_DIR_RE.match(name)
        if m and os.path.isdir(os.path.join(load_dir, name)):
            out.append((int(m.group(1)), os.path.join(load_dir, name)))
    out.sort(reverse=True)
    return out


def gc_checkpoints(save_dir: str, keep_latest_n: int,
                   protect: Iterable[str] = ()) -> List[str]:
    """Retention GC: keep the newest `keep_latest_n` COMPLETE iteration
    checkpoints, delete every older iter_* directory — including torn
    debris below the retention horizon. Never touches `release`, the
    tracker, or any path in `protect` (the checkpoint currently being
    written and the one a resume read from). Returns the deleted paths."""
    if keep_latest_n is None or keep_latest_n < 1:
        return []
    protect = {os.path.abspath(p) for p in protect}
    complete = [(it, p) for it, p in list_iteration_checkpoints(save_dir)
                if is_checkpoint_complete(p)]
    keep = {os.path.abspath(p) for _, p in complete[:keep_latest_n]}
    if complete:
        horizon = complete[min(keep_latest_n, len(complete)) - 1][0]
    else:
        return []  # nothing certified complete yet: delete nothing
    deleted = []
    for it, p in list_iteration_checkpoints(save_dir):
        ap = os.path.abspath(p)
        if ap in keep or ap in protect:
            continue
        if it >= horizon:
            # newer-than-horizon incomplete dirs may be an in-flight
            # async save on another manager: leave them alone
            continue
        try:
            shutil.rmtree(p)
            deleted.append(p)
        except OSError as e:
            print(f"WARNING: checkpoint GC could not delete {p}: {e}",
                  flush=True)
    return deleted


def _config_meta(model_cfg) -> dict:
    d = dataclasses.asdict(model_cfg)
    return {k: (str(v) if not isinstance(v, (int, float, bool, str, type(None), list, tuple)) else v)
            for k, v in d.items()}


class CheckpointArchMismatch(ValueError):
    """Raised on checkpoint-vs-config architecture mismatch. A distinct
    type so load_checkpoint's torn-save backward scan can re-raise it
    (user error) while falling back on arbitrary restore failures —
    tensorstore raises plain ValueError for corrupt data too."""


def check_checkpoint_args(saved: dict, model_cfg) -> None:
    """ref: check_checkpoint_args (checkpointing.py:35-66) — error on
    architecture mismatch."""
    current = _config_meta(model_cfg)
    critical = (
        "num_layers", "hidden_size", "num_attention_heads",
        "num_attention_heads_kv", "ffn_hidden_size", "padded_vocab_size",
        "position_embedding_type", "glu_activation", "use_rms_norm",
        "use_bias", "tie_embed_logits", "parallel_attn", "parallel_layernorm",
    )
    for k in critical:
        if k in saved and saved[k] != current[k]:
            raise CheckpointArchMismatch(
                f"checkpoint/config mismatch for {k}: "
                f"checkpoint has {saved[k]!r}, config has {current[k]!r}"
            )


def _build_meta(iteration, model_cfg, scheduler_state,
                consumed_train_samples, rng_key, extra_meta) -> dict:
    meta = {
        "iteration": iteration,
        "consumed_train_samples": consumed_train_samples,
        "scheduler": scheduler_state or {},
        "config": _config_meta(model_cfg) if model_cfg is not None else {},
        "rng_key": np.asarray(jax.random.key_data(rng_key)).tolist()
        if rng_key is not None else None,
        "checkpoint_version": 3.0,
    }
    meta.update(extra_meta or {})
    return meta


def _opt_state_tree(opt_state) -> dict:
    return {"step": opt_state.step, "m": opt_state.m,
            **({"v": opt_state.v} if opt_state.v is not None else {}),
            **({"scaler": opt_state.scaler}
               if getattr(opt_state, "scaler", None) else {})}


def save_checkpoint(
    save_dir: str,
    iteration: int,
    params: Any,
    opt_state: Any = None,
    model_cfg=None,
    scheduler_state: Optional[dict] = None,
    consumed_train_samples: int = 0,
    rng_key: Optional[jax.Array] = None,
    extra_meta: Optional[dict] = None,
    release: bool = False,
) -> str:
    """Synchronous save (ref: save_checkpoint checkpointing.py:243-338;
    `release=True` writes the converter layout, ref "release" naming
    :93). Blocks until committed; the train loop uses CheckpointManager
    instead so the step time only pays the device→host copy. Both paths
    share the crash-safe layout: COMPLETE sentinel last, atomic
    tracker."""
    save_dir = os.path.abspath(save_dir)  # orbax requires absolute paths
    path = checkpoint_dir(save_dir, iteration, release=release)
    # the iteration's own directory too: orbax makes it on its own
    # thread, and meta.json below is written before that save is waited on
    os.makedirs(path, exist_ok=True)
    ckptr = ocp.StandardCheckpointer()
    ckptr.save(os.path.join(path, "model"), params, force=True)
    if opt_state is not None:
        ckptr.save(os.path.join(path, "optim"), _opt_state_tree(opt_state),
                   force=True)
    meta = _build_meta(iteration, model_cfg, scheduler_state,
                       consumed_train_samples, rng_key, extra_meta)
    with open(os.path.join(path, "meta.json"), "w") as f:
        json.dump(meta, f, indent=1)
    ckptr.wait_until_finished()
    _mark_complete(path)
    _write_tracker(save_dir, iteration, release=release)
    return path


class CheckpointManager:
    """Async crash-safe checkpoint writer for ONE save directory.

    `save()` hands the on-device arrays to orbax's async path (two
    AsyncCheckpointers so the model and optimizer device→host copies
    overlap instead of serializing behind each other's commit) and
    returns to the train loop immediately; a background finalizer thread
    waits for the tensorstore commits, then writes meta.json, the
    COMPLETE sentinel (last), the atomic tracker, and runs retention GC.
    Exactly ONE save is in flight: a new `save()` first waits on the
    previous finalizer, so checkpoints can never interleave and the
    tracker only ever advances over certified-complete directories.

    `last_blocked_ms` is the wall time the caller was actually stalled
    by the most recent `save()` (previous-save wait + device→host copy)
    — exported as the `ckpt_blocked_ms` timers gauge by the trainer
    (no cell of `benchmark/` saves a checkpoint: the stall is not
    measured on the chip). Call `wait_until_finished()` (or `close()`) before
    process exit so the final save commits."""

    def __init__(self, save_dir: str, keep_latest_n: Optional[int] = None,
                 async_save: bool = True, recorder=None):
        self.save_dir = os.path.abspath(save_dir)
        self.keep_latest_n = keep_latest_n
        self.async_save = async_save
        # optional telemetry.FlightRecorder (ISSUE 13): the save
        # lifecycle (dispatch + blocked ms, background certification)
        # lands in the flight ring keyed by iteration — a postmortem
        # shows whether the run died inside/behind a save
        self.recorder = recorder
        self._model_ckptr = ocp.StandardCheckpointer()
        self._optim_ckptr = ocp.StandardCheckpointer()
        self._finalizer: Optional[threading.Thread] = None
        self._error: Optional[BaseException] = None
        self._inflight_path: Optional[str] = None
        # the checkpoint a resume read from — GC must never delete it
        self._protected: set = set()
        self.last_blocked_ms: float = 0.0
        self.total_blocked_ms: float = 0.0
        self.saves: int = 0

    def protect(self, path: Optional[str]) -> None:
        if path:
            self._protected.add(os.path.abspath(path))

    def _raise_pending(self) -> None:
        if self._error is not None:
            err, self._error = self._error, None
            raise RuntimeError(
                f"previous async checkpoint save failed: {err!r}") from err

    def wait_until_finished(self) -> None:
        """Block until the in-flight save (if any) is fully committed —
        the ONLY place the train loop ever pays the full write latency,
        and it only calls it at exit/rollback. Re-raises a background
        save failure loudly."""
        if self._finalizer is not None:
            self._finalizer.join()
            self._finalizer = None
        self._inflight_path = None
        self._raise_pending()

    close = wait_until_finished

    def _finalize(self, path: str, iteration: int, meta: dict) -> None:
        try:
            self._model_ckptr.wait_until_finished()
            self._optim_ckptr.wait_until_finished()
            if jax.process_index() == 0:
                with open(os.path.join(path, "meta.json"), "w") as f:
                    json.dump(meta, f, indent=1)
                _mark_complete(path)  # LAST artifact: certifies the save
                _write_tracker(self.save_dir, iteration)
                if self.keep_latest_n:
                    gc_checkpoints(
                        self.save_dir, self.keep_latest_n,
                        protect=self._protected | {path})
            if self.recorder is not None:
                self.recorder.record("ckpt_certified", step=iteration)
        except BaseException as e:  # surfaced on the next save()/wait()
            if self.recorder is not None:
                self.recorder.record("ckpt_failed", step=iteration,
                                     error=repr(e))
            self._error = e

    def save(
        self,
        iteration: int,
        params: Any,
        opt_state: Any = None,
        model_cfg=None,
        scheduler_state: Optional[dict] = None,
        consumed_train_samples: int = 0,
        rng_key: Optional[jax.Array] = None,
        extra_meta: Optional[dict] = None,
    ) -> str:
        t0 = time.perf_counter()
        # single in-flight: the previous save must be certified before a
        # newer one may start (tracker ordering + bounded host memory)
        self.wait_until_finished()
        path = checkpoint_dir(self.save_dir, iteration)
        os.makedirs(self.save_dir, exist_ok=True)
        if not self.async_save:
            out = save_checkpoint(
                self.save_dir, iteration, params, opt_state, model_cfg,
                scheduler_state, consumed_train_samples, rng_key,
                extra_meta)
            # retention holds in BOTH modes — sync saves certify
            # inline, so GC runs inline too
            if self.keep_latest_n and jax.process_index() == 0:
                gc_checkpoints(self.save_dir, self.keep_latest_n,
                               protect=self._protected | {path})
            self.last_blocked_ms = (time.perf_counter() - t0) * 1e3
            self.total_blocked_ms += self.last_blocked_ms
            self.saves += 1
            if self.recorder is not None:
                self.recorder.record(
                    "ckpt_certified", step=iteration,
                    blocked_ms=round(self.last_blocked_ms, 3))
            return out
        # async: these return after the device→host copy; tensorstore
        # writes + the directory rename happen on orbax's threads
        self._model_ckptr.save(os.path.join(path, "model"), params,
                               force=True)
        if opt_state is not None:
            self._optim_ckptr.save(os.path.join(path, "optim"),
                                   _opt_state_tree(opt_state), force=True)
        meta = _build_meta(iteration, model_cfg, scheduler_state,
                           consumed_train_samples, rng_key, extra_meta)
        self._inflight_path = path
        self._finalizer = threading.Thread(
            target=self._finalize, args=(path, iteration, meta),
            name=f"ckpt-finalize-{iteration}", daemon=False)
        self._finalizer.start()
        self.last_blocked_ms = (time.perf_counter() - t0) * 1e3
        self.total_blocked_ms += self.last_blocked_ms
        self.saves += 1
        if self.recorder is not None:
            self.recorder.record(
                "ckpt_dispatched", step=iteration,
                blocked_ms=round(self.last_blocked_ms, 3))
        return path


# The ARCHITECTURE fields --use_checkpoint_args may overlay — exactly the
# check_checkpoint_args critical set plus the shape-determining extras.
# Training knobs (dropout, recompute, flash, seq_length, ...) stay with
# the CLI, matching the reference's _set_arg force-list
# (ref: load_args_from_checkpoint checkpointing.py:506-560).
_CHECKPOINT_ARCH_FIELDS = (
    "num_layers", "hidden_size", "num_attention_heads",
    "num_attention_heads_kv", "kv_channels", "ffn_hidden_size",
    "padded_vocab_size", "position_embedding_type", "glu_activation",
    "hidden_act", "use_rms_norm", "use_bias", "tie_embed_logits",
    "parallel_attn", "parallel_layernorm", "use_post_ln",
    "layernorm_epsilon", "rope_theta", "rope_scaling_factor",
    "max_position_embeddings", "num_tokentypes", "add_binary_head",
)


def load_model_config_from_checkpoint(load_dir: str, mcfg):
    """Overlay the architecture recorded in a checkpoint's meta.json onto
    `mcfg` (ref: load_args_from_checkpoint checkpointing.py:476-560 +
    --use_checkpoint_args). Only the architecture fields listed above are
    taken (training knobs keep their CLI values); None round-trips.
    Returns the updated config, or the input unchanged when no
    checkpoint/meta exists."""
    iteration, release = read_tracker(load_dir)
    if iteration is None and not release:
        return mcfg
    meta_path = os.path.join(
        checkpoint_dir(load_dir, iteration or 0, release=release),
        "meta.json",
    )
    if not os.path.exists(meta_path):
        return mcfg
    with open(meta_path) as f:
        saved = json.load(f).get("config", {})
    updates = {}
    for name in _CHECKPOINT_ARCH_FIELDS:
        if name not in saved or not hasattr(mcfg, name):
            continue
        val = saved[name]
        cur = getattr(mcfg, name)
        if not isinstance(val, (int, float, bool, str, type(None))):
            continue
        if val is None or cur is None:
            if val != cur:
                updates[name] = val
        elif val != cur:
            updates[name] = type(cur)(val)
    if updates:
        print(f" > using checkpoint args from {meta_path}: "
              f"{sorted(updates)}", flush=True)
        mcfg = dataclasses.replace(mcfg, **updates)
    return mcfg


def _load_candidates(load_dir: str):
    """Resume candidates (newest first) plus the `intended` resume
    iteration. Ordering is strictly by iteration, NOT tracker-first: a
    crash between the COMPLETE sentinel and the tracker write leaves the
    tracker one save stale, and preferring it would silently discard a
    fully certified newer checkpoint. Directories without the COMPLETE
    sentinel are skipped (torn saves) — unless NO directory in load_dir
    has one (a pre-sentinel legacy layout), in which case everything is
    attempted and corruption is caught at restore time instead.
    `intended` — what a fully healthy directory would have resumed (the
    newer of tracker target and newest directory) — drives the caller's
    resumed-from-older warning."""
    tracker_iter, release = read_tracker(load_dir)
    iters = list_iteration_checkpoints(load_dir)
    any_sentinel = any(is_checkpoint_complete(p) for _, p in iters)
    out: List[Tuple[Optional[int], str, bool]] = []
    if release:
        out.append((None, checkpoint_dir(load_dir, 0, release=True), True))
    for it, path in iters:
        if any_sentinel and not is_checkpoint_complete(path):
            print(f"WARNING: skipping incomplete checkpoint {path} "
                  f"(no {COMPLETE_FILENAME} sentinel — torn save)",
                  flush=True)
            continue
        out.append((it, path, False))
    newest = iters[0][0] if iters else None
    intended = max((x for x in (tracker_iter, newest) if x is not None),
                   default=None)
    return out, intended


def _abstract_leaf(x):
    """Template leaf -> restore target. Sharding-less abstract leaves
    (jax.eval_shape output) get an explicit default-device sharding:
    with sharding=None orbax reads the sharding file instead and
    resurrects the SAVED topology, which is exactly wrong for cross-mesh
    restore. Callers that care where the bytes land (the trainer, the
    server tool) pass templates that carry their own shardings."""
    if (isinstance(x, jax.ShapeDtypeStruct)
            and getattr(x, "sharding", None) is None):
        return jax.ShapeDtypeStruct(
            x.shape, x.dtype,
            sharding=jax.sharding.SingleDeviceSharding(jax.devices()[0]))
    return ocp.utils.to_shape_dtype_struct(x)


def _restore_one(path: str, release: bool, params_template,
                 opt_state_template, model_cfg, finetune: bool,
                 no_load_optim: bool, no_load_rng: bool):
    """Restore a single checkpoint directory; raises on torn/corrupt
    artifacts (the caller's backward scan catches and falls back).
    Architecture mismatches raise CheckpointArchMismatch PAST the scan —
    a wrong --num_layers is a user error, not a torn save."""
    with open(os.path.join(path, "meta.json")) as f:
        meta = json.load(f)
    if model_cfg is not None and meta.get("config"):
        check_checkpoint_args(meta["config"], model_cfg)

    ckptr = ocp.StandardCheckpointer()
    abstract_params = jax.tree.map(_abstract_leaf, params_template)
    params = ckptr.restore(os.path.join(path, "model"), abstract_params)

    # release checkpoints (converter output) carry weights only: load like
    # --finetune — no optimizer/rng, iteration 0 (ref: checkpointing.py:
    # 583-625, release naming :93)
    opt_state = None
    if (opt_state_template is not None and not finetune and not no_load_optim
            and not release):
        from megatron_llm_tpu.optimizer.optimizer import OptimizerState

        tmpl = {"step": opt_state_template.step, "m": opt_state_template.m}
        if opt_state_template.v is not None:
            tmpl["v"] = opt_state_template.v
        if getattr(opt_state_template, "scaler", None):
            tmpl["scaler"] = opt_state_template.scaler
        abstract_opt = jax.tree.map(_abstract_leaf, tmpl)
        restored = ckptr.restore(os.path.join(path, "optim"), abstract_opt)
        opt_state = OptimizerState(
            step=restored["step"], m=restored["m"], v=restored.get("v"),
            scaler=restored.get("scaler"),
        )

    # --finetune resets iteration and skips optim/rng (ref :583-625)
    out_iteration = 0 if (finetune or release) else meta["iteration"]
    if finetune or no_load_rng or release:
        meta = dict(meta)
        meta["rng_key"] = None
    return params, opt_state, meta, out_iteration


def load_checkpoint(
    load_dir: str,
    params_template: Any,
    opt_state_template: Any = None,
    model_cfg=None,
    finetune: bool = False,
    no_load_optim: bool = False,
    no_load_rng: bool = False,
    iteration: Optional[int] = None,
):
    """ref: load_checkpoint (checkpointing.py:561-730).

    Templates are abstract (jax.eval_shape / ShapeDtypeStruct with sharding)
    or concrete trees; orbax restores into the template's shardings, so the
    same checkpoint loads under any mesh. Returns
    (params, opt_state|None, meta, iteration), plus `loaded_path` on the
    meta dict (retention GC protects it).

    Fault tolerance: when the tracker (or newest directory) names a torn
    or corrupt save — missing meta.json, partial orbax arrays, missing
    COMPLETE sentinel — the scan falls BACK through older complete
    checkpoints with a loud warning per skip. A preempted pod therefore
    always resumes from the newest certified checkpoint; it never
    crashes on the one the preemption tore. An explicitly requested
    `iteration` is exempt from the scan (you asked for that one: a
    problem with it is an error)."""
    load_dir = os.path.abspath(load_dir)  # orbax requires absolute paths

    if iteration is not None:
        path = checkpoint_dir(load_dir, iteration)
        out = _restore_one(path, False, params_template,
                           opt_state_template, model_cfg, finetune,
                           no_load_optim, no_load_rng)
        out[2]["loaded_path"] = path
        return out

    candidates, intended = _load_candidates(load_dir)
    if not candidates:
        return None  # no checkpoint (ref returns 0 + warns)

    for it, path, release in candidates:
        try:
            out = _restore_one(path, release, params_template,
                               opt_state_template, model_cfg, finetune,
                               no_load_optim, no_load_rng)
        except CheckpointArchMismatch:
            raise  # user error, not a torn save
        except Exception as e:  # noqa: BLE001 — any torn artifact
            print(f"WARNING: checkpoint at {path} is unreadable "
                  f"({type(e).__name__}: {e}); falling back to the "
                  f"previous complete checkpoint", flush=True)
            continue
        if it is not None and intended is not None and it < intended:
            print(f"WARNING: resumed from OLDER checkpoint {path} — the "
                  f"newer one(s) were torn or corrupt (a preemption "
                  f"mid-save?); training replays from iteration "
                  f"{out[3]}", flush=True)
        out[2]["loaded_path"] = path
        return out

    print(f"WARNING: no loadable checkpoint in {load_dir} "
          f"({len(candidates)} candidate(s), all torn/corrupt); "
          f"starting from scratch", flush=True)
    return None

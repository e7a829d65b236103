"""Checkpoint/auto-resume on orbax — first-class, unlike the reference.

Kubeflow leaves checkpointing to user code on PVCs (SURVEY.md §5.4); the
platform's only resume stories are Katib's DB resume and KFP's step cache.
Here every training job checkpoints through this manager (async, sharded,
multi-host-safe via orbax), and the JAXJob controller restarts processes
with `restore=latest` — checkpoint-restart IS the elasticity mechanism
(§5.3: world-resize in JAX means recompile, so v1 elasticity = resume).
"""

from __future__ import annotations

import logging
import os
import shutil
from typing import Any

import orbax.checkpoint as ocp

from kubeflow_tpu.utils import faults, resilience

_FP_SAVE = faults.register_point(
    "checkpoint.save", "before a checkpoint save lands; ctx: step")
_FP_RESTORE = faults.register_point(
    "checkpoint.restore", "before a checkpoint restore; ctx: step")

_LOG = logging.getLogger(__name__)

#: Subdirectory (inside the checkpoint root) where corrupt step dirs are
#: moved. Non-numeric, so orbax's step scan ignores it; kept on disk (not
#: deleted) so an operator can post-mortem the torn write.
QUARANTINE_DIR = "quarantine"

#: Marker orbax puts in its in-flight save directories
#: (`<step>.orbax-checkpoint-tmp-<n>`). One left on disk at manager init
#: is torn garbage from a killed attempt.
_TMP_MARKER = ".orbax-checkpoint-tmp-"


def _sweep_stale_tmp(directory: str) -> list[str]:
    """Delete torn `*.orbax-checkpoint-tmp-*` dirs under `directory`.

    A kill mid-async-save (the elastic-downsize SIGKILL path) leaves the
    in-flight tmp dir behind; the relaunched attempt then re-saves the
    same step and the collision can abort the writer natively — no
    Python traceback, just a signal exit that the controller reads as
    yet another worker failure and answers with a second (spurious)
    downsize. At manager init no save can be in flight — the gang
    restarts as a unit — so anything matching the marker is garbage.
    Per-entry errors are swallowed: gang peers may sweep concurrently,
    and a tmp dir we cannot remove only costs what it always did."""
    swept: list[str] = []
    try:
        entries = os.listdir(directory)
    except OSError:
        return swept
    for name in entries:
        if _TMP_MARKER not in name:
            continue
        try:
            shutil.rmtree(os.path.join(directory, name))
        except OSError:
            continue
        swept.append(name)
    return swept


class CheckpointManager:
    """Thin wrapper over ocp.CheckpointManager for TrainState pytrees."""

    def __init__(self, directory: str, *, interval: int = 100,
                 keep: int = 3, async_save: bool = True):
        self.directory = str(directory)
        self.interval = interval
        self._keep = keep
        self._async_save = async_save
        swept = _sweep_stale_tmp(self.directory)
        if swept:
            resilience.metrics.inc("tpk_checkpoint_tmp_swept_total",
                                   float(len(swept)), component="train")
            _LOG.warning(
                "swept %d torn orbax tmp dir(s) under %s: %s",
                len(swept), self.directory, ", ".join(sorted(swept)))
        options = ocp.CheckpointManagerOptions(
            save_interval_steps=interval,
            max_to_keep=keep,
            enable_async_checkpointing=async_save,
        )
        self._mgr = ocp.CheckpointManager(self.directory, options=options)

    def maybe_save(self, step: int, state: Any, *, data_state: Any = None,
                   force: bool = False) -> bool:
        """Save if `step` hits the interval (orbax enforces the schedule).
        `data_state` is the input iterator's resume state (a small JSON
        dict from grain get_state()) saved alongside the TrainState so
        resume continues the exact data stream (SURVEY.md §5.4)."""
        faults.fire(_FP_SAVE, step=step)
        items = {"state": ocp.args.StandardSave(state)}
        if data_state is not None:
            items["data"] = ocp.args.JsonSave(data_state)
        return self._mgr.save(step, args=ocp.args.Composite(**items),
                              force=force)

    def latest_step(self) -> int | None:
        return self._mgr.latest_step()

    def all_steps(self) -> list[int]:
        return list(self._mgr.all_steps())

    def should_save(self, step: int) -> bool:
        """Whether `step` is on the save schedule — lets the trainer skip
        collecting iterator state on the steps that won't save."""
        return self._mgr.should_save(step)

    def _items(self, step: int) -> list:
        """Item names in a step's checkpoint. Legacy (single-item) layouts
        yield None metadata → []; real metadata errors propagate so a
        transient failure doesn't silently misroute restore()."""
        meta = self._mgr.item_metadata(step)
        if meta is None:
            return []
        return list(getattr(meta, "keys", lambda: [])())

    def restore(self, state_template: Any, step: int | None = None) -> Any:
        """Restore into the (possibly abstract/sharded) template. Returns the
        template untouched when no checkpoint exists. Checkpoints written
        before the composite (state+data) layout restore via the legacy
        single-item path, so an upgraded runtime still resumes older jobs."""
        step = step if step is not None else self.latest_step()
        if step is None:
            return state_template
        faults.fire(_FP_RESTORE, step=step)
        if "state" not in self._items(step):
            return self._mgr.restore(
                step, args=ocp.args.StandardRestore(state_template))
        out = self._mgr.restore(step, args=ocp.args.Composite(
            state=ocp.args.StandardRestore(state_template)))
        return out["state"]

    def quarantine_step(self, step: int) -> str | None:
        """Move `step`'s directory into `<root>/quarantine/` so the next
        latest_step() skips it — a partial orbax write (SIGKILL mid-save,
        torn disk) must cost one checkpoint interval, not wedge every
        restart on the same poisoned restore. Returns the new path."""
        src = os.path.join(self.directory, str(step))
        if not os.path.isdir(src):
            return None
        qdir = os.path.join(self.directory, QUARANTINE_DIR)
        os.makedirs(qdir, exist_ok=True)
        dst = os.path.join(qdir, str(step))
        n = 1
        while os.path.exists(dst):
            dst = os.path.join(qdir, f"{step}.{n}")
            n += 1
        os.rename(src, dst)
        resilience.metrics.inc("tpk_checkpoint_quarantined_total",
                               component="train")
        self._mgr.reload()  # refresh the manager's cached step list
        return dst

    def restore_latest_good(self, state_template: Any
                            ) -> tuple[Any, int | None, list[int]]:
        """Restore the newest step that actually restores, quarantining
        any that raise (partial write, bad metadata) and falling back to
        the next-newest — so a torn checkpoint costs one interval of
        recompute instead of burning the whole backoff budget on a
        permanently poisoned restore. Returns (state, step, quarantined);
        (template, None, [...]) when nothing restorable remains.

        Elastic-resize contract: steps on disk may have been written by
        a DIFFERENT fsdp topology — orbax saves logical arrays and
        restores into whatever shardings `state_template` carries, so
        the template's (current) mesh governs and the fallback chain is
        topology-agnostic. A SIGKILL mid-save of the first post-resize
        checkpoint therefore quarantines that torn step and lands on the
        last good PRE-resize step, resharding it on the way in
        (tests/test_faults.py pins the crash-during-resize case)."""
        quarantined: list[int] = []
        while True:
            step = self.latest_step()
            if step is None:
                return state_template, None, quarantined
            try:
                return self.restore(state_template, step), step, quarantined
            except (KeyboardInterrupt, SystemExit):
                raise
            except Exception as e:
                resilience.metrics.inc("tpk_checkpoint_fallback_total",
                                       component="train")
                dst = self.quarantine_step(step)
                if self.latest_step() == step:
                    # Quarantine didn't remove the step from the scan
                    # (non-local storage, unexpected step-dir layout):
                    # surfacing the restore error beats looping on the
                    # same poisoned step forever.
                    raise RuntimeError(
                        f"checkpoint step {step} failed to restore and "
                        f"could not be quarantined under "
                        f"{self.directory}") from e
                quarantined.append(int(step))
                _LOG.warning(
                    "checkpoint step %s failed to restore (%s: %s); "
                    "quarantined to %s, falling back to the next-newest "
                    "step", step, type(e).__name__, e, dst)

    def restore_data_state(self, step: int | None = None) -> Any | None:
        """The saved input-iterator state, or None when the checkpoint
        predates it (plain-generator jobs) or the item is unreadable
        (the trainer then falls back to replaying the stream)."""
        step = step if step is not None else self.latest_step()
        if step is None:
            return None
        try:
            has_data = "data" in self._items(step)
        except Exception:
            return None  # worst case: the trainer falls back to replay
        if not has_data:
            return None
        try:
            out = self._mgr.restore(
                step, args=ocp.args.Composite(data=ocp.args.JsonRestore()))
        except Exception:
            # A torn `data` item must not kill a resume whose TrainState
            # already restored — replaying the stream is the safe floor.
            return None
        return out["data"]

    def wait(self) -> None:
        self._mgr.wait_until_finished()

    def close(self) -> None:
        self._mgr.wait_until_finished()
        self._mgr.close()

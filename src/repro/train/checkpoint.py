"""Fault-tolerant checkpointing with reshard-on-restore.

Rubick's reconfiguration mechanism is checkpoint-resume (paper Sec 5.2/6):
a reconfigured job saves a checkpoint, restarts with a new plan/allocation,
and restores — so restore must work onto a DIFFERENT mesh/plan than the one
that saved (elastic scaling).  Params/opt-state are saved as plain named
arrays; on restore each leaf is re-placed under the new shardings.

Layout:  <dir>/step_<n>/{arrays.npz, meta.json}   (atomic via tmp+rename)

``meta.json`` is the latest-checkpoint pointer (``list_steps`` keys on
its existence), so its write path is crash-safe: contents land in a tmp
file that is fsynced, atomically renamed into place, and the directory
rename that publishes the whole step is fsynced through the parent — a
crash mid-save can never leave a torn pointer, only the previous intact
checkpoint.  ``restore_cost_estimate`` prices a restart from real pytree
sizes with the same bandwidth model the simulator charges for simulated
failures (``memory.restore_seconds``).
"""

from __future__ import annotations

import json
import os
import shutil
import tempfile
import threading
from pathlib import Path
from time import perf_counter
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np


def _flatten(tree: Any) -> dict[str, np.ndarray]:
    import jax.tree_util as jtu
    flat, _ = jtu.tree_flatten_with_path(tree)
    out = {}
    for path, leaf in flat:
        key = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                       for k in path)
        arr = np.asarray(jax.device_get(leaf))
        if arr.dtype == jnp.bfloat16:
            out[key + "::bf16"] = arr.view(np.uint16)
        else:
            out[key] = arr
    return out


def _unflatten_into(template: Any, arrays: dict[str, np.ndarray]) -> Any:
    import jax.tree_util as jtu
    flat, treedef = jtu.tree_flatten_with_path(template)
    leaves = []
    for path, leaf in flat:
        key = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                       for k in path)
        if key + "::bf16" in arrays:
            arr = arrays[key + "::bf16"].view(jnp.bfloat16)
        elif key in arrays:
            arr = arrays[key]
        else:
            raise KeyError(f"checkpoint missing leaf {key!r}")
        if tuple(arr.shape) != tuple(leaf.shape):
            raise ValueError(f"shape mismatch for {key}: "
                             f"{arr.shape} vs {leaf.shape}")
        leaves.append(arr)
    return jtu.tree_unflatten(treedef, leaves)


class CheckpointManager:
    def __init__(self, directory: str | Path, keep_last: int = 3,
                 async_save: bool = True, recorder: Any | None = None):
        self.dir = Path(directory)
        self.dir.mkdir(parents=True, exist_ok=True)
        self.keep_last = keep_last
        self.async_save = async_save
        self.recorder = recorder       # flight recorder (repro.obs), opt-in
        self._thread: threading.Thread | None = None
        self._error: BaseException | None = None

    # ------------------------------------------------------------------
    def save(self, step: int, params: Any, opt_state: Any | None = None,
             meta: dict | None = None, block: bool = False) -> Path:
        """Atomic save; async by default so training overlaps the write."""
        self.wait()
        arrays = _flatten({"params": params,
                           **({"opt": opt_state} if opt_state is not None
                              else {})})
        meta = dict(meta or {})
        meta["step"] = step
        target = self.dir / f"step_{step:09d}"

        def _write():
            t0 = perf_counter()
            tmp = Path(tempfile.mkdtemp(dir=self.dir, prefix=".tmp_"))
            np.savez(tmp / "arrays.npz", **arrays)
            # meta.json is the latest-checkpoint pointer: write-to-temp +
            # fsync + atomic rename so a crash mid-write can never leave
            # a torn (half-written) manifest that list_steps would trust
            mtmp = tmp / ".meta.json.tmp"
            with open(mtmp, "w") as f:
                f.write(json.dumps(meta))
                f.flush()
                os.fsync(f.fileno())
            os.replace(mtmp, tmp / "meta.json")
            if target.exists():
                shutil.rmtree(target)
            os.replace(tmp, target)
            # publish durably: the directory rename itself must survive a
            # power loss, or the pointer points at nothing after reboot
            fd = os.open(self.dir, os.O_RDONLY)
            try:
                os.fsync(fd)
            finally:
                os.close(fd)
            self._gc()
            if self.recorder is not None:
                nbytes = sum(a.nbytes for a in arrays.values())
                self.recorder.span("checkpoint-save", t0, perf_counter(),
                                   float(step), bytes=nbytes)

        def _write_async():
            try:
                _write()
            except BaseException as e:
                self._error = e

        if self.async_save and not block:
            self._thread = threading.Thread(target=_write_async, daemon=True)
            self._thread.start()
        else:
            _write()
        return target

    def wait(self) -> None:
        """Join the async writer; re-raise whatever it raised."""
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        if self._error is not None:
            err, self._error = self._error, None
            raise err

    @staticmethod
    def restore_cost_estimate(params: Any,
                              opt_state: Any | None = None) -> float:
        """Seconds a restart from this state would cost: total checkpoint
        bytes (every param/opt leaf) through the shared restore-bandwidth
        model — the same formula the simulator charges simulated failures
        via ``memory.restore_cost(profile=...)`` (there, sized
        analytically from the model profile instead of live arrays)."""
        from repro.core.memory import restore_cost
        nbytes = 0
        leaves = jax.tree.leaves({"params": params,
                                  **({"opt": opt_state}
                                     if opt_state is not None else {})})
        for leaf in leaves:
            nbytes += int(np.prod(np.shape(leaf))) \
                * np.dtype(getattr(leaf, "dtype", np.float32)).itemsize
        return restore_cost(nbytes=float(nbytes))

    def _gc(self) -> None:
        steps = self.list_steps()
        for s in steps[:-self.keep_last]:
            shutil.rmtree(self.dir / f"step_{s:09d}", ignore_errors=True)

    # ------------------------------------------------------------------
    def list_steps(self) -> list[int]:
        out = []
        for p in self.dir.glob("step_*"):
            if (p / "meta.json").exists():
                out.append(int(p.name.split("_")[1]))
        return sorted(out)

    def latest_step(self) -> int | None:
        steps = self.list_steps()
        return steps[-1] if steps else None

    def restore(self, params_template: Any, opt_template: Any | None = None,
                step: int | None = None,
                shardings: Any | None = None, opt_shardings: Any | None = None,
                ) -> tuple[Any, Any | None, dict]:
        """Restore onto possibly-different shardings (elastic restart)."""
        t0 = perf_counter()
        self.wait()
        step = step if step is not None else self.latest_step()
        if step is None:
            raise FileNotFoundError(f"no checkpoints in {self.dir}")
        d = self.dir / f"step_{step:09d}"
        arrays = dict(np.load(d / "arrays.npz"))
        meta = json.loads((d / "meta.json").read_text())
        params = _unflatten_into({"params": params_template}, arrays)["params"]
        if shardings is not None:
            params = jax.tree.map(
                lambda a, s: jax.device_put(a, s), params, shardings)
        opt = None
        if opt_template is not None:
            opt = _unflatten_into({"opt": opt_template}, arrays)["opt"]
            if opt_shardings is not None:
                opt = jax.tree.map(
                    lambda a, s: jax.device_put(a, s), opt, opt_shardings)
        if self.recorder is not None:
            self.recorder.span("checkpoint-restore", t0, perf_counter(),
                               float(step))
        return params, opt, meta

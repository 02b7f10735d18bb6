"""Checkpoint manager for the join service's durable state (DESIGN.md §16):
atomic and resumable.

* Atomic: state is written to ``step_XXXXXXXX.tmp/`` then renamed — a crash
  mid-save never corrupts the latest checkpoint (rename is the commit point).
  Replacing an existing step first renames the old dir aside
  (``step_XXXXXXXX.old``) so a crash anywhere inside ``_write`` always
  leaves at least one restorable copy of that step on disk.
* Content: flat ``{path: np.ndarray}`` arrays (npz shards) + a JSON manifest
  with step, caller extras, and tree structure.  Trees may contain
  registered dataclasses (e.g. the serve layer's ``SessionState`` pytrees):
  array fields land in the npz, non-array scalar fields (static pytree
  metadata like ``n_objects``) land in the manifest, and the manifest
  records the fully-qualified class per subtree so ``restore`` rebuilds the
  dataclass instances.
* Sidecar: ``save(..., sidecar={...})`` writes an additional
  ``sidecar.json`` inside the step dir under the same commit point — the
  serve layer uses it for gateway/ledger state that is JSON, not arrays.
* Host-side: restore loads every array on the host; callers place them on
  devices themselves.
* Async: ``save(..., background=True)`` hands the host copy to a writer
  thread so the caller overlaps the disk write.  A failed background
  write is never silent: the exception is captured and re-raised from
  ``wait()`` or the next ``save``/``restore``.
"""
from __future__ import annotations

import dataclasses
import importlib
import json
import os
import shutil
import threading
import time
from pathlib import Path
from typing import Any, Dict, Optional, Tuple

import ml_dtypes
import numpy as np

_BFLOAT16 = np.dtype(ml_dtypes.bfloat16)
_STATIC_TYPES = (bool, int, float, str, type(None))


def _class_name(obj: Any) -> str:
    cls = type(obj)
    return f"{cls.__module__}.{cls.__qualname__}"


def _resolve_class(name: str) -> type:
    mod, _, qual = name.rpartition(".")
    obj: Any = importlib.import_module(mod)
    for part in qual.split("."):
        obj = getattr(obj, part)
    return obj


def _flatten(tree: Any, prefix: str = "",
             statics: Optional[Dict[str, Any]] = None,
             classes: Optional[Dict[str, str]] = None) -> Dict[str, Any]:
    """Flatten nested dicts / dataclasses into ``{path: array}``.  Dataclass
    fields that are plain scalars (static metadata) go into ``statics``;
    the dataclass's import path goes into ``classes`` keyed by subtree."""
    out: Dict[str, Any] = {}
    if isinstance(tree, dict):
        for k in sorted(tree):
            out.update(_flatten(tree[k], f"{prefix}{k}/", statics, classes))
    elif dataclasses.is_dataclass(tree) and not isinstance(tree, type):
        if classes is not None:
            classes[prefix[:-1]] = _class_name(tree)
        for f in sorted(dataclasses.fields(tree), key=lambda f: f.name):
            v = getattr(tree, f.name)
            if isinstance(v, _STATIC_TYPES):
                if statics is not None:
                    statics[f"{prefix}{f.name}"] = v
            else:
                out.update(_flatten(v, f"{prefix}{f.name}/",
                                    statics, classes))
    else:
        out[prefix[:-1]] = tree
    return out


def _unflatten(flat: Dict[str, Any],
               statics: Optional[Dict[str, Any]] = None,
               classes: Optional[Dict[str, str]] = None) -> Any:
    root: Dict[str, Any] = {}

    def _insert(path: str, v: Any) -> None:
        node = root
        parts = path.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = v

    for path, v in flat.items():
        _insert(path, v)
    for path, v in (statics or {}).items():
        _insert(path, v)
    # materialise dataclasses deepest-first so nested instances exist
    # before their parents are constructed
    for path in sorted(classes or {}, key=lambda p: -p.count("/")):
        cls = _resolve_class((classes or {})[path])
        if path == "":
            return cls(**root)
        node = root
        parts = path.split("/")
        for p in parts[:-1]:
            node = node[p]
        node[parts[-1]] = cls(**node[parts[-1]])
    return root


class CheckpointManager:
    def __init__(self, directory: str | Path, keep: int = 3):
        self.dir = Path(directory)
        self.dir.mkdir(parents=True, exist_ok=True)
        self.keep = keep
        self._thread: Optional[threading.Thread] = None
        self._error: Optional[BaseException] = None

    # ---------------- save ----------------
    def save(self, step: int, state: Any, extra: Optional[dict] = None,
             background: bool = False,
             sidecar: Optional[dict] = None) -> Path:
        self.wait()  # joins a previous writer and re-raises its failure
        statics: Dict[str, Any] = {}
        classes: Dict[str, str] = {}
        flat = _flatten(state, statics=statics, classes=classes)
        host = {}
        dtypes: Dict[str, str] = {}
        for k, v in flat.items():
            a = np.asarray(v)
            if a.dtype == _BFLOAT16:
                # npz can't round-trip ml_dtypes.bfloat16 — store raw bits
                dtypes[k] = "bfloat16"
                a = a.view(np.uint16)
            host[k] = a
        args = (step, host, extra or {}, dtypes, statics, classes, sidecar)
        if background:
            self._thread = threading.Thread(
                target=self._write_guarded, args=args, daemon=True)
            self._thread.start()
            return self.dir / f"step_{step:08d}"
        return self._write(*args)

    def _write_guarded(self, *args) -> None:
        try:
            self._write(*args)
        except BaseException as e:  # surfaced by wait() / the next save
            self._error = e

    def _write(self, step: int, host: Dict[str, np.ndarray], extra: dict,
               dtypes: Dict[str, str], statics: Dict[str, Any],
               classes: Dict[str, str],
               sidecar: Optional[dict] = None) -> Path:
        final = self.dir / f"step_{step:08d}"
        tmp = self.dir / f"step_{step:08d}.tmp"
        old = self.dir / f"step_{step:08d}.old"
        if tmp.exists():
            shutil.rmtree(tmp)
        tmp.mkdir(parents=True)
        np.savez(tmp / "arrays.npz", **host)
        if sidecar is not None:
            (tmp / "sidecar.json").write_text(json.dumps(sidecar))
        manifest = {
            "step": step,
            "keys": sorted(host),
            "dtypes": dtypes,
            "statics": statics,
            "classes": classes,
            "extra": extra,
            "time": time.time(),
        }
        (tmp / "manifest.json").write_text(json.dumps(manifest))
        # Replace-in-place without a window where no valid copy of this
        # step exists: park the previous dir aside, commit, then drop it.
        if old.exists():
            shutil.rmtree(old)
        if final.exists():
            os.rename(final, old)
        os.rename(tmp, final)          # commit point
        if old.exists():
            shutil.rmtree(old)
        self._gc()
        return final

    def wait(self):
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        if self._error is not None:
            err, self._error = self._error, None
            raise RuntimeError("background checkpoint save failed") from err

    def _gc(self):
        ckpts = self.all_steps()
        for s in ckpts[: max(0, len(ckpts) - self.keep)]:
            shutil.rmtree(self.dir / f"step_{s:08d}", ignore_errors=True)
            shutil.rmtree(self.dir / f"step_{s:08d}.old", ignore_errors=True)

    # ---------------- restore ----------------
    @staticmethod
    def _valid(d: Path) -> bool:
        return (d / "manifest.json").exists()

    def all_steps(self) -> list:
        out = set()
        for p in self.dir.glob("step_*"):
            name = p.name
            if name.endswith(".tmp"):
                continue
            if name.endswith(".old"):
                # a parked dir only counts when the commit never landed
                s = int(name[len("step_"):-len(".old")])
                if self._valid(p) and \
                        not self._valid(self.dir / f"step_{s:08d}"):
                    out.add(s)
                continue
            if self._valid(p):
                out.add(int(name.split("_")[1]))
        return sorted(out)

    def latest_step(self) -> Optional[int]:
        steps = self.all_steps()
        return steps[-1] if steps else None

    def _step_dir(self, step: int) -> Path:
        final = self.dir / f"step_{step:08d}"
        if self._valid(final):
            return final
        old = self.dir / f"step_{step:08d}.old"
        if self._valid(old):
            return old
        raise FileNotFoundError(f"no restorable checkpoint for step {step} "
                                f"in {self.dir}")

    def sidecar(self, step: Optional[int] = None) -> Optional[dict]:
        """The JSON sidecar saved alongside ``step`` (latest by default),
        or None if that checkpoint has none."""
        self.wait()
        if step is None:
            step = self.latest_step()
        if step is None:
            raise FileNotFoundError(f"no checkpoints in {self.dir}")
        p = self._step_dir(step) / "sidecar.json"
        return json.loads(p.read_text()) if p.exists() else None

    def restore(self, step: Optional[int] = None) -> Tuple[int, Any, dict]:
        """Returns (step, state, extra), every array a host numpy array."""
        self.wait()
        if step is None:
            step = self.latest_step()
        if step is None:
            raise FileNotFoundError(f"no checkpoints in {self.dir}")
        d = self._step_dir(step)
        manifest = json.loads((d / "manifest.json").read_text())
        dtypes = manifest.get("dtypes", {})
        with np.load(d / "arrays.npz") as z:
            flat = {}
            for k in manifest["keys"]:
                a = z[k]
                if dtypes.get(k) == "bfloat16":
                    a = a.view(_BFLOAT16)
                flat[k] = a
        state = _unflatten(flat, manifest.get("statics", {}),
                           manifest.get("classes", {}))
        return step, state, manifest.get("extra", {})

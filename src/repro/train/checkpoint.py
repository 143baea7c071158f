"""Self-healing checkpointing: atomic npz + JSON manifest, keep-k, resume.

Layout:  <dir>/step_<N>/arrays.npz + manifest.json, plus <dir>/LATEST
pointing at the newest complete checkpoint. Writes go to a tmp directory
that is fsynced and atomically renamed, so a killed writer never corrupts
state — restart-safe (the paper's cluster reality: preemptions mid-save).

Hardening beyond atomicity (docs/robustness.md):

* every array carries a CRC32 checksum in the manifest, verified on
  restore — a bit-flipped or truncated ``arrays.npz`` is detected, not
  silently loaded;
* writes fsync file contents AND the containing directories before the
  atomic rename commits, so a power loss cannot leave a renamed-but-empty
  checkpoint;
* transient write failures retry with capped, seeded-jittered
  exponential backoff (``CheckpointConfig.write_retries`` /
  ``retry_max_backoff_s`` / ``retry_jitter``; :func:`retry_delays`)
  before the error propagates — the chaos engine's ``ckpt_io`` fault
  injects exactly here;
* restore walks back to the last *verified-good* ``step_*`` dir when the
  requested checkpoint is corrupt, and ``latest_step`` falls back to
  scanning existing step dirs when ``LATEST`` dangles — good checkpoints
  on disk are never stranded by a bad pointer.

The saved tree includes params, optimizer state, EMA, data-pipeline state,
and the aggregation config (N, b, W) — elastic restarts with a different
worker count re-shard and re-scale the lr (repro.train.elastic).
"""
from __future__ import annotations

import json
import os
import shutil
import tempfile
import time
import zipfile
import zlib
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import numpy as np


class CheckpointCorruption(RuntimeError):
    """Raised when no verified-good checkpoint could be restored."""


def _flatten_with_paths(tree: Any) -> Dict[str, np.ndarray]:
    flat = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        key = "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
        flat[key] = np.asarray(jax.device_get(leaf))
    return flat


def _to_storable(flat: Dict[str, np.ndarray]) -> Dict[str, str]:
    """npz keeps numpy's own dtypes only: an extension dtype such as
    bfloat16 comes back as raw void bytes. Such arrays are stored as
    same-width unsigned ints (in place in ``flat``); returns their real
    dtype names, which the manifest keeps for the load to view back."""
    dtypes = {}
    for k, v in flat.items():
        if v.dtype.kind == "V":
            dtypes[k] = v.dtype.name
            flat[k] = v.view(f"u{v.dtype.itemsize}")
    return dtypes


def _unflatten_like(template: Any, flat: Dict[str, np.ndarray]) -> Any:
    paths_and_leaves, treedef = jax.tree_util.tree_flatten_with_path(template)
    leaves = []
    for path, leaf in paths_and_leaves:
        key = "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
        if key not in flat:
            raise KeyError(f"checkpoint missing array {key!r}")
        arr = flat[key]
        if tuple(arr.shape) != tuple(leaf.shape):
            raise ValueError(f"shape mismatch for {key}: ckpt {arr.shape} "
                             f"vs template {leaf.shape}")
        leaves.append(arr.astype(leaf.dtype))
    return jax.tree_util.tree_unflatten(treedef, leaves)


def _checksum(arr: np.ndarray) -> str:
    """CRC32 over dtype, shape and raw bytes (cheap, catches truncation
    and bit flips — not an adversarial-integrity hash)."""
    meta = f"{arr.dtype.str}:{arr.shape}".encode()
    crc = zlib.crc32(np.ascontiguousarray(arr).tobytes(), zlib.crc32(meta))
    return f"crc32:{crc:08x}"


def _fsync_path(path: str) -> None:
    fd = os.open(path, os.O_RDONLY)
    try:
        os.fsync(fd)
    finally:
        os.close(fd)


def _write_attempt(tmp: str, flat: Dict[str, np.ndarray], manifest: Dict,
                   io_check: Optional[Callable[[], None]]) -> None:
    """One durable write of arrays + manifest into ``tmp`` (no rename)."""
    if io_check is not None:
        io_check()                 # chaos engine's ckpt_io injection point
    with open(os.path.join(tmp, "arrays.npz"), "wb") as f:
        np.savez(f, **flat)
        f.flush()
        os.fsync(f.fileno())
    with open(os.path.join(tmp, "manifest.json"), "w") as f:
        json.dump(manifest, f, indent=2, default=str)
        f.flush()
        os.fsync(f.fileno())
    _fsync_path(tmp)


def retry_delays(retries: int, backoff_s: float, *,
                 max_backoff_s: float = 0.25, jitter: float = 0.5,
                 seed: int = 0) -> List[float]:
    """The seeded retry-delay schedule ``save`` sleeps through.

    Exponential backoff capped at ``max_backoff_s``, then scaled by a
    uniform jitter in ``[1, 1 + jitter]`` so a fleet of writers that
    failed together does not retry together (the classic thundering-herd
    fix). The jitter stream is seeded — the same ``seed`` yields the
    identical schedule, which keeps chaos runs replayable.
    """
    rng = np.random.RandomState(seed)
    out = []
    for attempt in range(max(retries, 0)):
        delay = min(backoff_s * (2 ** attempt), max_backoff_s)
        out.append(delay * (1.0 + jitter * float(rng.uniform())))
    return out


def save(directory: str, step: int, tree: Any, metadata: Optional[Dict] = None,
         keep: int = 3, *, retries: int = 3, backoff_s: float = 0.01,
         max_backoff_s: float = 0.25, jitter: float = 0.5,
         backoff_seed: int = 0,
         io_check: Optional[Callable[[], None]] = None,
         on_retry: Optional[Callable[[int, BaseException], None]] = None,
         sleep: Callable[[float], None] = time.sleep) -> str:
    """Write one checkpoint durably and atomically.

    ``io_check`` is called at the start of every write attempt and may
    raise ``OSError`` (fault injection / preflight quota checks). Failed
    attempts retry up to ``retries`` times with jittered exponential
    backoff — capped at ``max_backoff_s``, scaled by a seeded uniform
    jitter in ``[1, 1 + jitter]`` (see :func:`retry_delays`) — with
    ``on_retry(attempt, exc)`` observing each, then re-raise.
    """
    os.makedirs(directory, exist_ok=True)
    final = os.path.join(directory, f"step_{step:08d}")
    flat = _flatten_with_paths(tree)
    dtypes = _to_storable(flat)
    manifest = {"step": step, "arrays": sorted(flat),
                "checksums": {k: _checksum(v) for k, v in flat.items()},
                **({"dtypes": dtypes} if dtypes else {}),
                **(metadata or {})}
    delays = retry_delays(retries, backoff_s, max_backoff_s=max_backoff_s,
                          jitter=jitter, seed=backoff_seed)
    attempt = 0
    while True:
        tmp = tempfile.mkdtemp(dir=directory, prefix=".tmp_ckpt_")
        try:
            _write_attempt(tmp, flat, manifest, io_check)
            if os.path.exists(final):
                shutil.rmtree(final)
            os.rename(tmp, final)              # atomic commit
            _fsync_path(directory)
            break
        except OSError as e:
            shutil.rmtree(tmp, ignore_errors=True)
            if attempt >= len(delays):
                raise
            if on_retry is not None:
                on_retry(attempt, e)
            sleep(delays[attempt])
            attempt += 1
        except BaseException:
            shutil.rmtree(tmp, ignore_errors=True)
            raise
    with open(os.path.join(directory, "LATEST.tmp"), "w") as f:
        f.write(os.path.basename(final))
        f.flush()
        os.fsync(f.fileno())
    os.replace(os.path.join(directory, "LATEST.tmp"),
               os.path.join(directory, "LATEST"))
    _fsync_path(directory)
    _cleanup(directory, keep)
    return final


def _cleanup(directory: str, keep: int) -> None:
    steps = sorted(d for d in os.listdir(directory) if d.startswith("step_"))
    for d in steps[:-keep] if keep > 0 else []:
        shutil.rmtree(os.path.join(directory, d), ignore_errors=True)
    # sweep tmp dirs abandoned by writers killed mid-save
    for d in os.listdir(directory):
        if d.startswith(".tmp_ckpt_"):
            shutil.rmtree(os.path.join(directory, d), ignore_errors=True)


def available_steps(directory: str) -> List[int]:
    """Steps of every complete-looking checkpoint dir (manifest present),
    ascending — what the walk-back fallback iterates over."""
    if not os.path.isdir(directory):
        return []
    out = []
    for d in sorted(os.listdir(directory)):
        if d.startswith("step_") and os.path.exists(
                os.path.join(directory, d, "manifest.json")):
            try:
                out.append(int(d.split("_")[1]))
            except ValueError:
                continue
    return out


def latest_step(directory: str) -> Optional[int]:
    """Newest checkpoint step. The ``LATEST`` pointer is a hint; when it
    is missing or dangles (points at a deleted/missing dir) the existing
    ``step_*`` dirs are scanned instead of failing restores while good
    checkpoints exist on disk."""
    latest = os.path.join(directory, "LATEST")
    if os.path.exists(latest):
        with open(latest) as f:
            name = f.read().strip()
        if os.path.isdir(os.path.join(directory, name)):
            try:
                return int(name.split("_")[1])
            except (IndexError, ValueError):
                pass
    steps = available_steps(directory)
    return steps[-1] if steps else None


def _load_verified(directory: str, step: int
                   ) -> Tuple[Dict[str, np.ndarray], Dict]:
    """Load and checksum-verify one checkpoint; raises CheckpointCorruption
    on any integrity failure (unreadable manifest/zip, missing arrays,
    checksum mismatch). Checkpoints written before checksums existed
    verify by array presence only."""
    path = os.path.join(directory, f"step_{step:08d}")
    try:
        with open(os.path.join(path, "manifest.json")) as f:
            manifest = json.load(f)
        with np.load(os.path.join(path, "arrays.npz")) as z:
            flat = {k: z[k] for k in z.files}
    except (OSError, ValueError, json.JSONDecodeError, zipfile.BadZipFile,
            zlib.error, EOFError) as e:
        raise CheckpointCorruption(f"step {step}: {e}") from e
    missing = [k for k in manifest.get("arrays", []) if k not in flat]
    if missing:
        raise CheckpointCorruption(f"step {step}: arrays {missing} listed in "
                                   f"manifest but absent from arrays.npz")
    for k, want in manifest.get("checksums", {}).items():
        if k not in flat:
            raise CheckpointCorruption(f"step {step}: checksummed array "
                                       f"{k!r} missing")
        got = _checksum(flat[k])
        if got != want:
            raise CheckpointCorruption(
                f"step {step}: checksum mismatch for {k!r} "
                f"({got} != manifest {want})")
    for k, name in manifest.get("dtypes", {}).items():
        flat[k] = flat[k].view(np.dtype(name))
    return flat, manifest


def verify(directory: str, step: int) -> bool:
    """True iff the checkpoint at ``step`` passes integrity verification."""
    try:
        _load_verified(directory, step)
        return True
    except CheckpointCorruption:
        return False


def find_good_step(directory: str, step: Optional[int] = None
                   ) -> Optional[int]:
    """The newest verified-good step <= ``step`` (or <= latest). Walks
    back over existing ``step_*`` dirs past corrupt ones; None when no
    checkpoint verifies."""
    if step is None:
        step = latest_step(directory)
        if step is None:
            return None
    for s in reversed([s for s in available_steps(directory) if s <= step]):
        if verify(directory, s):
            return s
    return None


def read_manifest(directory: str, step: Optional[int] = None) -> Dict:
    """The checkpoint's manifest alone (no array load) — for callers that
    must shape their restore template from saved metadata first."""
    if step is None:
        step = latest_step(directory)
        if step is None:
            raise FileNotFoundError(f"no checkpoint under {directory}")
    path = os.path.join(directory, f"step_{step:08d}")
    with open(os.path.join(path, "manifest.json")) as f:
        return json.load(f)


def restore(directory: str, template: Any, step: Optional[int] = None,
            *, fallback: bool = True) -> Tuple[Any, Dict]:
    """Returns (tree, manifest). template supplies structure/shapes/dtypes.

    Every candidate checkpoint is checksum-verified before use. On
    corruption the restore walks back to the last verified-good
    ``step_*`` dir (``fallback=False`` pins the requested step instead).
    Template mismatches (missing key / wrong shape) always raise — they
    are caller errors, not disk corruption.
    """
    start = step if step is not None else latest_step(directory)
    if start is None:
        raise FileNotFoundError(f"no checkpoint under {directory}")
    candidates = ([start] if not fallback else
                  list(reversed([s for s in available_steps(directory)
                                 if s <= start])) or [start])
    errors = []
    for s in candidates:
        try:
            flat, manifest = _load_verified(directory, s)
        except CheckpointCorruption as e:
            errors.append(str(e))
            continue
        return _unflatten_like(template, flat), manifest
    raise CheckpointCorruption(
        f"no verified-good checkpoint under {directory} "
        f"(tried steps {candidates}): " + "; ".join(errors))

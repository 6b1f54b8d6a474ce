"""Crash-safe disk tier for the executor cache (DESIGN.md §14).

A daemon fleet means rolling restarts: without persistence every new
process repays the full cold-compile cost (~20x request latency per
``bench_serve``).  ``DiskTier`` persists compiled bucket executables via
JAX AOT export/serialization, one file per ``ExecKey``, so a restarted
daemon starts warm — and it is built for the failure model, not the
happy path:

* **Atomic writes.**  Entries are written to a tmp file in the same
  directory and ``os.replace``d into place, so a SIGKILL mid-persist
  can never leave a half-written entry under a valid name.
* **Per-entry checksum.**  The serialized payload's sha256 rides in the
  header; a corrupt entry (bit rot, torn write, injected fault) fails
  verification on load and is *quarantined* (renamed aside) and
  recompiled — never loaded, never fatal.
* **Invalidation in the header.**  JAX version, backend platform, and
  the full ``ExecKey`` field dict must match on load; stale entries are
  quarantined like corrupt ones.  Entries whose placement needs more
  devices than the loading process has are skipped (left on disk —
  they are valid for a bigger sibling, just not usable here).
* **Size-budgeted LRU.**  ``store`` evicts oldest-used entries (mtime,
  refreshed on every load hit) past ``budget_bytes``.

Entry format (single file, ``<sha256(key_str)[:40]>.spx``)::

    SPXC1\n
    {json header: format, key fields, key_str, jax, backend, sha256, nbytes}\n
    <serialized executable bytes>

``store`` failures are counted, never raised: ``jax.export`` may refuse
an executable (e.g. unserializable custom calls) and the cache must keep
serving from memory regardless — persistence is an optimization, the
compile path is the fallback.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import threading
from typing import Callable

import jax
from jax import export as jax_export   # not an auto-loaded jax attribute

from repro.kernels import autotune

from .plan import ExecKey, placement_grid

MAGIC = b"SPXC1\n"
SUFFIX = ".spx"
QUAR_SUFFIX = ".quar"
DEFAULT_BUDGET_BYTES = 1 << 30          # 1 GiB: blobs are KB-scale


def exec_key_str(key: ExecKey) -> str:
    """Canonical string form of an ``ExecKey`` — the disk identity."""
    return "|".join(f"{f.name}={getattr(key, f.name)}"
                    for f in dataclasses.fields(ExecKey))


def _named_call(exported) -> Callable:
    """``exported.call`` under the exported function's own name, so the
    restored program's XLA module reads as the stored one did
    (``jit_spatter_gather_pallas``) and not ``jit_call``."""
    def call(*args):
        return exported.call(*args)
    call.__name__ = call.__qualname__ = exported.fun_name
    return call


class RestoredExecutable:
    """A deserialized AOT executable, marked with its provenance.

    The restored callable traces to one opaque exported call (its jaxpr
    is a single ``pjit`` wrapping ``call_exported``), so trace-inspecting
    lint rules cannot see inside it; auditors check ``restored`` and fall
    back to key-only rules (analysis/lint.py).
    """
    restored = True
    __slots__ = ("_fn",)

    def __init__(self, fn: Callable):
        self._fn = fn

    def __call__(self, *args):
        return self._fn(*args)


class DiskTier:
    """One directory of serialized executables keyed by ``ExecKey``.

    Thread safety: counters are guarded by an internal lock; file I/O
    runs outside it (the OS-level atomicity of ``os.replace`` is the
    real concurrency contract — two processes racing a store of the
    same key both write whole entries, last replace wins).

    ``mangle`` is the fault-injection seam: when set, it may corrupt
    the payload AFTER the checksum is computed, so an injected
    disk-corruption fault is exactly what the checksum must catch.
    """

    def __init__(self, root: str, *,
                 budget_bytes: int = DEFAULT_BUDGET_BYTES,
                 mangle: Callable[[bytes], bytes] | None = None):
        self.root = os.path.abspath(root)
        os.makedirs(self.root, exist_ok=True)
        self.budget_bytes = int(budget_bytes)
        self._mangle = mangle
        self._lock = threading.Lock()
        self.loads = 0              # successful restores
        self.load_misses = 0        # no entry on disk
        self.stores = 0
        self.store_failures = 0     # export refused / write failed
        self.quarantined = 0        # corrupt or stale entries set aside
        self.skipped = 0            # valid but needs more devices
        self.evicted = 0

    # -- paths ---------------------------------------------------------------
    def path_for(self, key: ExecKey) -> str:
        digest = hashlib.sha256(exec_key_str(key).encode()).hexdigest()
        return os.path.join(self.root, digest[:40] + SUFFIX)

    def _count(self, attr: str) -> None:
        with self._lock:
            setattr(self, attr, getattr(self, attr) + 1)

    def _quarantine(self, path: str) -> None:
        try:
            os.replace(path, path + QUAR_SUFFIX)
        except OSError:
            pass
        self._count("quarantined")

    # -- store ---------------------------------------------------------------
    def store(self, key: ExecKey, fn: Callable, avals: tuple) -> bool:
        """Serialize ``fn`` (traced at ``avals``) under ``key``.

        Returns True on success; every failure path counts
        ``store_failures`` and returns False — persistence must never
        take down a serving process that already holds the executable
        in memory.
        """
        if getattr(fn, "restored", False):
            return False                 # came FROM disk: already there
        try:
            # tracing the executable consults the tile autotuner for
            # exactly the tiles it bakes in; record them so a restore can
            # re-seed the memo and skip the search (DESIGN.md §16)
            with autotune.recording() as tiles:
                exported = jax_export.export(fn)(*avals)
            payload = bytes(exported.serialize())
        except Exception:
            self._count("store_failures")
            return False
        header = {
            "format": 1,
            "key": dataclasses.asdict(key),
            "key_str": exec_key_str(key),
            "jax": jax.__version__,
            "backend": jax.default_backend(),
            "sha256": hashlib.sha256(payload).hexdigest(),
            "nbytes": len(payload),
        }
        if tiles:
            header["tiles"] = autotune.to_wire(tiles)
        if self._mangle is not None:     # injected corruption (post-checksum)
            payload = self._mangle(payload)
        path = self.path_for(key)
        tmp = path + f".tmp.{os.getpid()}.{threading.get_ident()}"
        try:
            with open(tmp, "wb") as f:
                f.write(MAGIC)
                f.write(json.dumps(header, sort_keys=True).encode())
                f.write(b"\n")
                f.write(payload)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, path)
        except OSError:
            self._count("store_failures")
            try:
                os.unlink(tmp)
            except OSError:
                pass
            return False
        self._count("stores")
        self._evict_to_budget()
        return True

    # -- load ----------------------------------------------------------------
    def load(self, key: ExecKey) -> Callable | None:
        """Restore ``key``'s executable, or None (miss / quarantined /
        incompatible).  Never raises: any entry that cannot be fully
        verified and deserialized is quarantined and reported as a miss,
        so the caller recompiles."""
        path = self.path_for(key)
        try:
            with open(path, "rb") as f:
                raw = f.read()
        except OSError:
            self._count("load_misses")
            return None
        return self._restore(path, raw, expect=key)

    def load_all(self) -> list[tuple[ExecKey, Callable]]:
        """Restore every verifiable entry (daemon startup preload).

        Corrupt/stale entries are quarantined as in ``load``; entries
        needing more devices than this process has are skipped.
        """
        out: list[tuple[ExecKey, Callable]] = []
        for name in sorted(self._entry_names()):
            path = os.path.join(self.root, name)
            try:
                with open(path, "rb") as f:
                    raw = f.read()
            except OSError:
                continue
            header = self._parse_header(path, raw)
            if header is None:
                continue
            try:
                key = ExecKey(**header["key"])
            except TypeError:
                self._quarantine(path)
                continue
            fn = self._restore(path, raw, expect=key, header=header)
            if fn is not None:
                out.append((key, fn))
        return out

    def _entry_names(self) -> list[str]:
        try:
            return [n for n in os.listdir(self.root) if n.endswith(SUFFIX)]
        except OSError:
            return []

    def _parse_header(self, path: str, raw: bytes) -> dict | None:
        if not raw.startswith(MAGIC):
            self._quarantine(path)
            return None
        nl = raw.find(b"\n", len(MAGIC))
        if nl < 0:
            self._quarantine(path)
            return None
        try:
            header = json.loads(raw[len(MAGIC):nl])
        except ValueError:
            self._quarantine(path)
            return None
        if not isinstance(header, dict) or header.get("format") != 1:
            self._quarantine(path)
            return None
        header["_payload_off"] = nl + 1
        return header

    def _restore(self, path: str, raw: bytes, *, expect: ExecKey,
                 header: dict | None = None) -> Callable | None:
        if header is None:
            header = self._parse_header(path, raw)
            if header is None:
                return None
        # stale: different key under this filename, or another toolchain
        if (header.get("key_str") != exec_key_str(expect)
                or header.get("jax") != jax.__version__
                or header.get("backend") != jax.default_backend()):
            self._quarantine(path)
            return None
        payload = raw[header["_payload_off"]:]
        if (header.get("nbytes") != len(payload)
                or header.get("sha256")
                != hashlib.sha256(payload).hexdigest()):
            self._quarantine(path)
            return None
        # valid entry, but its placement wants more devices than we have
        try:
            ndev = placement_grid(expect.placement)[2]
        except ValueError:
            self._quarantine(path)
            return None
        if ndev > len(jax.devices()):
            self._count("skipped")
            return None
        try:
            exported = jax_export.deserialize(payload)
            fn = jax.jit(_named_call(exported))
        except Exception:
            self._quarantine(path)
            return None
        # re-seed the tile memo with the choices this entry baked in, so
        # a warm restart never re-runs the autotune search
        autotune.seed_wire(header.get("tiles"))
        try:
            os.utime(path)              # LRU recency for the byte budget
        except OSError:
            pass
        self._count("loads")
        return RestoredExecutable(fn)

    # -- eviction ------------------------------------------------------------
    def _evict_to_budget(self) -> None:
        entries = []
        for name in self._entry_names():
            path = os.path.join(self.root, name)
            try:
                st = os.stat(path)
            except OSError:
                continue
            entries.append((st.st_mtime, st.st_size, path))
        total = sum(size for _, size, _ in entries)
        entries.sort()                  # oldest mtime first
        for _, size, path in entries:
            if total <= self.budget_bytes:
                break
            try:
                os.unlink(path)
            except OSError:
                continue
            total -= size
            self._count("evicted")

    # -- telemetry -----------------------------------------------------------
    def stats(self) -> dict:
        entries = self._entry_names()
        nbytes = 0
        for name in entries:
            try:
                nbytes += os.stat(os.path.join(self.root, name)).st_size
            except OSError:
                pass
        with self._lock:
            return {
                "root": self.root,
                "entries": len(entries),
                "bytes": nbytes,
                "budget_bytes": self.budget_bytes,
                "loads": self.loads,
                "load_misses": self.load_misses,
                "stores": self.stores,
                "store_failures": self.store_failures,
                "quarantined": self.quarantined,
                "skipped": self.skipped,
                "evicted": self.evicted,
            }

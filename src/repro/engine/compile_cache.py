"""Persistent compilation cache + startup warming for the jax batch path.

A long-lived service amortizes XLA trace+compile latency across requests —
until the process restarts and every hot (cfg, pad-class, batch-class)
signature pays it again, right on the latency-critical warm-up path.  This
module makes that state durable:

* **Signature manifest** — every fresh compile writes one small JSON file
  under ``{dir}/sigs/`` recording the (mechanism, cfg, majority_first,
  pad-class, batch-class) key and its observed compile time.  The manifest
  is the durable record of *what was hot*; replaying it re-traces each
  signature before a restarted worker admits traffic.
* **Serialized AOT executables** — the compiled executable itself is
  serialized (``jax.experimental.serialize_executable``) under
  ``{dir}/execs/``, so warming (and cold misses at serve time) deserialize
  instead of re-tracing at all.  Entries are keyed by the backend platform,
  device kind and jax version as well as the signature: an executable
  compiled for another backend is a miss, never a load error.

Both layers are written atomically (tmp file + ``os.replace``) with one
file per entry, so N shard processes can share one cache directory without
coordination: concurrent stores of the same signature are idempotent
last-writer-wins of identical content.

:class:`~repro.service.core.SimulationService` wires this up via its
``warm_start=`` argument; in the process tier only the device-owning shard
warms, because it is the only one that runs jax work.

:func:`install_jax_cache` is the other half: it places JAX's own persistent
compilation cache, and every entry point that compiles calls it first.

The module imports no jax at top level — installing a cache keeps
numpy-only deployments jax-free.
"""
from __future__ import annotations

import hashlib
import json
import os
import pickle
import tempfile
import threading
import time
import zlib
from dataclasses import dataclass, field
from typing import Any, Iterable

from repro.core.isa import MachineConfig

__all__ = [
    "affinity_token", "shard_of_token", "CompileCache", "WarmReport",
    "install_compile_cache", "installed_cache", "uninstall_compile_cache",
    "compile_cache_stats", "install_jax_cache",
]


# ---------------------------------------------------------------------------
# affinity hashing — shared by service routing and the manifest
# ---------------------------------------------------------------------------

def _canon_cfg(cfg: MachineConfig) -> str:
    return json.dumps(cfg._asdict(), sort_keys=True, separators=(",", ":"))


def affinity_token(mechanism: str, cfg: MachineConfig,
                   majority_first: bool, pad_len: int) -> str:
    """The stable routing token of one compiled-state locality class.

    Everything that shares a token shares jit/executable cache state
    (mechanism + canonical cfg + scheduling flavor + padding class).  The
    token is plain text and computed without touching jax, so a parent
    process can route with it — hash it with :func:`shard_of_token`, never
    with the builtin ``hash`` (randomized per process, useless across a
    pool).
    """
    return (f"{mechanism}|{_canon_cfg(cfg)}|mf{int(bool(majority_first))}"
            f"|pad{int(pad_len)}")


def shard_of_token(token: str, n_shards: int) -> int:
    """Deterministic shard assignment of a token: crc32 mod ``n_shards``."""
    if n_shards <= 1:
        return 0
    return zlib.crc32(token.encode("utf-8")) % n_shards


# ---------------------------------------------------------------------------
# JAX's persistent compilation cache
# ---------------------------------------------------------------------------

#: The checkout root: ``src/repro/engine/`` is three levels below it.
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def install_jax_cache() -> str:
    """Place JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is changed.  Otherwise the cache goes to the fixed
    ``<checkout>/.jax_cache``: the directory is part of what a later
    process must find again, so it is never derived from a temp dir, a
    pid or the time.  Entry points call this before they compile; importing
    the library never does.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    directory = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", directory)
    return directory


def _backend_key() -> str:
    """Platform, device kind and jax version of this process's backend —
    an executable serialized under one of them cannot load under another."""
    import jax
    dev = jax.devices()[0]
    return f"{dev.platform}|{dev.device_kind}|jax{jax.__version__}"


# ---------------------------------------------------------------------------
# cache entries
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class CacheEntry:
    """One manifest record: a hot compile-shape signature."""

    mechanism: str
    cfg: dict[str, Any]
    majority_first: bool
    batch: int
    pad_len: int
    token: str
    compile_time_s: float = 0.0

    def machine_config(self) -> MachineConfig:
        known = {k: v for k, v in self.cfg.items()
                 if k in MachineConfig._fields}
        return MachineConfig(**known)


@dataclass
class WarmReport:
    """Outcome of replaying the manifest in one process."""

    signatures: int = 0     # manifest entries replayed
    loaded: int = 0         # satisfied by a deserialized AOT executable
    retraced: int = 0       # had to trace+compile from scratch
    errors: int = 0
    wall_s: float = 0.0

    def as_dict(self) -> dict[str, Any]:
        return dict(self.__dict__)

    @staticmethod
    def from_dict(d: dict[str, Any]) -> "WarmReport":
        r = WarmReport()
        for k, v in d.items():
            if hasattr(r, k):
                setattr(r, k, v)
        return r


def _atomic_write(path: str, data: bytes) -> None:
    d = os.path.dirname(path)
    fd, tmp = tempfile.mkstemp(dir=d, prefix=".tmp-")
    try:
        with os.fdopen(fd, "wb") as f:
            f.write(data)
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise


@dataclass
class CompileCache:
    """One on-disk cache directory: ``sigs/*.json`` + ``execs/*.jaxexec``."""

    directory: str
    stats: dict[str, Any] = field(default_factory=lambda: {
        "stored": 0, "disk_hits": 0, "disk_misses": 0,
        "serialize_failures": 0, "load_errors": 0, "load_time_s": 0.0})

    def __post_init__(self) -> None:
        self.directory = os.path.abspath(self.directory)
        self._lock = threading.Lock()
        os.makedirs(self._sig_dir, exist_ok=True)
        os.makedirs(self._exec_dir, exist_ok=True)

    @property
    def _sig_dir(self) -> str:
        return os.path.join(self.directory, "sigs")

    @property
    def _exec_dir(self) -> str:
        return os.path.join(self.directory, "execs")

    # -- keying ----------------------------------------------------------

    @staticmethod
    def _digest(token: str, batch: int) -> str:
        return hashlib.sha1(f"{token}|b{int(batch)}"
                            .encode("utf-8")).hexdigest()[:20]

    def _sig_path(self, token: str, batch: int) -> str:
        return os.path.join(self._sig_dir,
                            f"{self._digest(token, batch)}.json")

    def _exec_path(self, token: str, batch: int) -> str:
        # the manifest records what was hot on any backend; an executable
        # only loads on the backend that compiled it, so its key names one
        digest = self._digest(f"{token}|{_backend_key()}", batch)
        return os.path.join(self._exec_dir, f"{digest}.jaxexec")

    # -- store / load ----------------------------------------------------

    def store_executable(self, mechanism: str, cfg: MachineConfig,
                         majority_first: bool, batch: int, pad_len: int,
                         compiled: Any, compile_time_s: float | None = None
                         ) -> bool:
        """Record a fresh compile: the manifest entry plus the serialized
        executable.  Returns whether the executable payload was persisted."""
        token = affinity_token(mechanism, cfg, majority_first, pad_len)
        entry = {"mechanism": mechanism, "cfg": cfg._asdict(),
                 "majority_first": bool(majority_first), "batch": int(batch),
                 "pad_len": int(pad_len), "token": token,
                 "compile_time_s": float(compile_time_s or 0.0)}
        _atomic_write(self._sig_path(token, batch),
                      json.dumps(entry, sort_keys=True).encode("utf-8"))
        wrote_exec = False
        try:
            from jax.experimental import serialize_executable as se
            payload, in_tree, out_tree = se.serialize(compiled)
            _atomic_write(self._exec_path(token, batch),
                          pickle.dumps((payload, in_tree, out_tree)))
            wrote_exec = True
        except Exception:
            with self._lock:
                self.stats["serialize_failures"] += 1
        with self._lock:
            self.stats["stored"] += 1
        return wrote_exec

    def has(self, mechanism: str, cfg: MachineConfig, majority_first: bool,
            batch: int, pad_len: int) -> bool:
        """Whether the manifest already records this signature."""
        token = affinity_token(mechanism, cfg, majority_first, pad_len)
        return os.path.exists(self._sig_path(token, batch))

    def load_executable(self, mechanism: str, cfg: MachineConfig,
                        majority_first: bool, batch: int, pad_len: int
                        ) -> Any | None:
        """A deserialized AOT executable for the signature on this
        backend, or ``None``."""
        token = affinity_token(mechanism, cfg, majority_first, pad_len)
        exec_path = self._exec_path(token, batch)
        if not os.path.exists(exec_path):
            with self._lock:
                self.stats["disk_misses"] += 1
            return None
        t0 = time.perf_counter()
        try:
            with open(exec_path, "rb") as f:
                payload, in_tree, out_tree = pickle.load(f)
            from jax.experimental import serialize_executable as se
            compiled = se.deserialize_and_load(payload, in_tree, out_tree)
        except Exception:
            with self._lock:
                self.stats["load_errors"] += 1
            return None
        with self._lock:
            self.stats["disk_hits"] += 1
            self.stats["load_time_s"] += time.perf_counter() - t0
        return compiled

    # -- manifest --------------------------------------------------------

    def entries(self) -> list[CacheEntry]:
        """All manifest entries, sorted by token then batch (stable warm
        order).  Corrupt files are skipped, not fatal."""
        out: list[CacheEntry] = []
        try:
            names = sorted(os.listdir(self._sig_dir))
        except OSError:
            return out
        for name in names:
            if not name.endswith(".json"):
                continue
            try:
                with open(os.path.join(self._sig_dir, name),
                          encoding="utf-8") as f:
                    d = json.load(f)
                out.append(CacheEntry(
                    mechanism=str(d["mechanism"]), cfg=dict(d["cfg"]),
                    majority_first=bool(d["majority_first"]),
                    batch=int(d["batch"]), pad_len=int(d["pad_len"]),
                    token=str(d["token"]),
                    compile_time_s=float(d.get("compile_time_s", 0.0))))
            except (OSError, ValueError, KeyError, TypeError):
                continue
        out.sort(key=lambda e: (e.token, e.batch))
        return out

    # -- warming ---------------------------------------------------------

    def warm(self, *, mechanisms: Iterable[str] = ("hanoi_jax",)
             ) -> WarmReport:
        """Replay the manifest through the adapter compile path, so every
        hot signature is compiled (deserialized where this backend's
        executable payload survives, re-traced otherwise) *before* the
        caller admits traffic."""
        from .adapters import _compiled_batch_exec, batch_cache_stats

        wanted = set(mechanisms)
        report = WarmReport()
        t0 = time.perf_counter()
        for entry in self.entries():
            if entry.mechanism not in wanted:
                continue
            report.signatures += 1
            before = batch_cache_stats()
            try:
                _compiled_batch_exec(entry.machine_config(),
                                     entry.majority_first, entry.batch,
                                     entry.pad_len)
            except Exception:
                report.errors += 1
                continue
            after = batch_cache_stats()
            if after["misses"] > before["misses"]:
                report.retraced += 1
            elif after["disk_hits"] > before["disk_hits"]:
                report.loaded += 1
            # a plain in-memory hit counts as neither — the signature
            # was already warm
        report.wall_s = time.perf_counter() - t0
        return report

    def snapshot(self) -> dict[str, Any]:
        with self._lock:
            snap = dict(self.stats)
        snap["manifest_entries"] = len(self.entries())
        return snap


# ---------------------------------------------------------------------------
# process-global installation (consulted by adapters._compiled_batch_exec)
# ---------------------------------------------------------------------------

_INSTALLED: CompileCache | None = None


def install_compile_cache(directory: str) -> CompileCache:
    """Install (or re-point) the process-global persistent cache."""
    global _INSTALLED
    _INSTALLED = CompileCache(directory)
    return _INSTALLED


def installed_cache() -> CompileCache | None:
    return _INSTALLED


def uninstall_compile_cache() -> None:
    global _INSTALLED
    _INSTALLED = None


def compile_cache_stats() -> dict[str, Any]:
    """One merged snapshot: in-memory batch-cache counters plus (when a
    persistent cache is installed) its disk-layer counters."""
    from .adapters import batch_cache_stats
    snap: dict[str, Any] = dict(batch_cache_stats())
    cache = installed_cache()
    if cache is not None:
        snap["disk"] = cache.snapshot()
    return snap

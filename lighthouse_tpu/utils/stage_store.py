"""A store of jitted device stages as serialized programs, kept across
processes.

JAX's persistent compile cache keeps only the backend compile: every new
process still traces a stage (Python -> jaxpr) and lowers it (jaxpr ->
MLIR) before the cache is even consulted. For the BLS stages that is most
of a node's start. ``StoredStage`` keeps each program a stage is called
with in ``jax.export``'s serialized form, in the ``stages`` subdirectory of
the compile cache directory the process uses. The first process that calls
a stage at given argument shapes traces and lowers it, as before, and
writes it; every later process reads it back and lowers only the thin call
around it, whose backend compile then hits the persistent cache. The miss
path compiles that same call, so both paths run one program.

An entry's key covers everything that decides what the stage traces to:
the stage name, every argument's shape and dtype, the platform, device kind
and platform version (libtpu's build on a TPU), the jax and jaxlib
versions, the x64 flag, a digest of every ``.py`` file of the package, every
``LIGHTHOUSE_*`` environment variable (the package's switches, some read at
trace time) and the stage's own trace-time settings. Any change traces
again. An entry is named by its whole key and holds a SHA-256 of its
payload; one that cannot be read, is cut short, fails its checksum or does
not match its stage is deleted, counted ``invalid`` and traced again.
Entries are written to a temporary file and moved into place with
``os.replace``, so concurrent writers never leave a torn entry. With no
compile cache directory (or the cache turned off) a stage traces as a plain
``jax.jit`` does.

Reading, writing and (de)serializing an entry is observed on
``jax_compile_phase_seconds{phase="load"}``; each resolution counts on
``bls_stage_store_total{stage, outcome=hit|miss|invalid}``.
"""

from __future__ import annotations

import contextlib
import functools
import hashlib
import json
import os
import tempfile
import threading

import jax
import jaxlib

from .logging import get_logger
from .tracing import STAGE_STORE_TOTAL, compile_phase

SUBDIR = "stages"
_MAGIC = b"LHTPUSTG"
_HEADER = len(_MAGIC) + 32     # magic, then the SHA-256 of the payload
_PACKAGE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

log = get_logger("stage_store")


class InvalidEntry(ValueError):
    """A stored entry that must not be loaded."""


def sources_digest(root: str) -> str:
    """SHA-256 over every ``.py`` file under ``root``: relative path and
    contents, in a fixed order."""
    h = hashlib.sha256()
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = sorted(d for d in dirnames if d != "__pycache__")
        for name in sorted(filenames):
            if not name.endswith(".py"):
                continue
            path = os.path.join(dirpath, name)
            h.update(os.path.relpath(path, root).encode() + b"\0")
            with open(path, "rb") as fh:
                h.update(hashlib.sha256(fh.read()).digest())
    return h.hexdigest()


@functools.cache
def package_digest() -> str:
    """``sources_digest`` of this package, once per process."""
    return sources_digest(_PACKAGE)


def stage_key(name: str, specs, settings=()) -> str:
    """Hex SHA-256 of everything a stored program depends on."""
    dev = jax.devices()[0]
    parts = {
        "stage": name,
        "args": [[list(s.shape), str(s.dtype)] for s in specs],
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "platform_version": dev.client.platform_version,
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "x64": bool(jax.config.jax_enable_x64),
        "sources": package_digest(),
        "env": sorted((k, v) for k, v in os.environ.items()
                      if k.startswith("LIGHTHOUSE_")),
        "settings": [list(map(str, s)) for s in settings],
    }
    return hashlib.sha256(json.dumps(parts, sort_keys=True).encode()).hexdigest()


def store_dir() -> str | None:
    """Where entries live: ``stages`` under the compile cache directory,
    or None where the process has no compile cache."""
    base = jax.config.jax_compilation_cache_dir
    if not base or not jax.config.jax_enable_compilation_cache:
        return None
    return os.path.join(base, SUBDIR)


def read_entry(path: str) -> bytes:
    """The payload stored at ``path``; FileNotFoundError if there is none,
    InvalidEntry if it must not be loaded."""
    with open(path, "rb") as fh:
        data = fh.read()
    if len(data) < _HEADER or not data.startswith(_MAGIC):
        raise InvalidEntry("short entry or bad magic")
    payload = data[_HEADER:]
    if hashlib.sha256(payload).digest() != data[len(_MAGIC):_HEADER]:
        raise InvalidEntry("payload checksum mismatch")
    return payload


def write_entry(path: str, payload: bytes) -> None:
    """Write atomically: a temporary file in the same directory, then
    ``os.replace``."""
    os.makedirs(os.path.dirname(path), exist_ok=True)
    fd, tmp = tempfile.mkstemp(dir=os.path.dirname(path), suffix=".tmp")
    try:
        with os.fdopen(fd, "wb") as fh:
            fh.write(_MAGIC + hashlib.sha256(payload).digest() + payload)
        os.replace(tmp, path)
    except BaseException:
        with contextlib.suppress(OSError):
            os.unlink(tmp)
        raise


def entry_path(root: str, name: str, key: str) -> str:
    """Where the entry of stage ``name`` under ``key`` lives."""
    return os.path.join(root, f"{name}-{key}.jaxexp")


class StoredStage:
    """A jitted stage whose programs come from the store.

    ``jitted`` is the stage as written; ``lower`` still lowers it, for the
    compile probes. Each call looks up the program for its arguments'
    shapes and dtypes, resolving it once per process: loaded from the
    store, or traced, exported and stored. ``settings`` are (name, value)
    pairs that decide how the stage traces besides its source, for the
    key."""

    def __init__(self, jitted, settings=()):
        self.jitted = jitted
        self.__name__ = jitted.__name__
        self.settings = tuple(settings)
        self._programs: dict = {}
        self._lock = threading.Lock()

    def lower(self, *args, **kwargs):
        return self.jitted.lower(*args, **kwargs)

    def __call__(self, *args):
        sig = tuple((a.shape, a.dtype) for a in args)
        program = self._programs.get(sig)
        if program is None:
            with self._lock:
                program = self._programs.get(sig)
                if program is None:
                    program = self._programs[sig] = self._resolve(args)
        return program(*args)

    def _resolve(self, args):
        root = store_dir()
        if root is None:
            return self.jitted
        name = self.__name__
        specs = [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in args]
        path = entry_path(root, name, stage_key(name, specs, self.settings))
        outcome, traced = "miss", None
        try:
            with compile_phase("load", name):
                traced = self._trace(read_entry(path), specs)
            outcome = "hit"
        except FileNotFoundError:
            pass
        except Exception as e:  # noqa: BLE001 — any bad entry is traced again
            log.warn("Stored stage invalid, tracing it again", stage=name,
                     path=path, error=repr(e))
            with contextlib.suppress(OSError):
                os.unlink(path)
            outcome = "invalid"
        if traced is None:
            exported = jax.export.export(self.jitted)(*specs)
            with compile_phase("load", name):
                payload = exported.serialize()
                try:
                    write_entry(path, payload)
                except OSError as e:
                    log.warn("Stage not stored", stage=name, path=path,
                             error=repr(e))
                traced = self._trace(payload, specs)
        STAGE_STORE_TOTAL.inc(stage=name, outcome=outcome)
        return traced.lower().compile()

    def _trace(self, payload, specs):
        """The stored program as one jitted call under the stage's name, so
        its module keeps the stage's name, traced at ``specs``. Both paths
        compile this call, never the stage as written."""
        exported = jax.export.deserialize(bytearray(payload))
        want = [(tuple(s.shape), s.dtype) for s in specs]
        have = [(tuple(a.shape), a.dtype) for a in exported.in_avals]
        if have != want:
            raise InvalidEntry(f"entry takes {have}, the call gives {want}")

        def call(*args):
            return exported.call(*args)

        call.__name__ = call.__qualname__ = self.__name__
        return jax.jit(call).trace(*specs)

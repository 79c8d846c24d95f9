"""AOT executable store: serialized XLA executables next to the cache.

The compile wall is the last cold-start cost the persistent compile
cache does not remove: a cache LOAD still re-runs XLA's deserialize +
link inside the first dispatch of every shape, and an empty cache pays
the full 314-357 s/shape compile on the serving path.  This module
stores the COMPILED executables themselves — ``jax.jit(...).lower()
.compile()`` once (``cli precompile``), ``jax.experimental
.serialize_executable`` the result to disk, and every later process
deserializes straight to a callable, skipping tracing, lowering and
XLA entirely.

Entries are keyed by (kernel name, extra key, argument signature) in
the file name and carry an identity header — jax version, backend
platform, device kind, device count, and a fingerprint of the kernel
source tree — checked at load: a mismatched or corrupt entry degrades
to a fresh compile with ONE WARN per complaint (the infra/env.py knob
contract, applied to blobs).  An entry built for another platform,
device count or device assignment than this process has is another
machine's, not a fault: it reads as a MISS and is overwritten.

``wrap()`` is the serving seam: it decorates a jitted callable so each
argument signature resolves ONCE per process — to the deserialized
store executable when present, to the wrapped jit otherwise — and the
load/miss counters let ``ops/provider.py`` classify a first dispatch
as ``aot_load`` alongside compile/cache_load.

Each resolution leaves ONE load record (``load_records()``, a bounded
list beside ``stats()``): which program, how it was come by
(``aot_load`` | ``cache_load`` | ``compile`` | ``jit``), the entry's
bytes, and the seconds of each cost a cold start pays for it — file
read, deserialize (of which decode), compile, store write, the first
call (waited for).
The provider lists the ones paid inside a first dispatch's enqueue in
that dispatch's ledger record (``compile.programs``).
"""

import hashlib
import logging
import os
import pickle
import threading
import time
import zlib
from collections import deque
from typing import Callable, List, Optional, Sequence, Tuple

from . import clock, tracing
from .env import env_int, env_str
from .metrics import GLOBAL_REGISTRY

_LOG = logging.getLogger(__name__)

ENV_DIR = "TEKU_TPU_AOT_STORE_DIR"
ENV_ON = "TEKU_TPU_AOT_STORE"
ENV_MAX_MB = "TEKU_TPU_AOT_STORE_MAX_MB"
_OFF_VALUES = ("off", "0", "none", "disabled")

# bump when the blob layout changes: old-format entries must read as
# a mismatch (one WARN + fresh compile), never unpickle garbage
# (2: entries carry the ids of the devices they execute on and are
# zlib-compressed — a serialized TPU executable is ~2x its code size and
# compresses ~5x, and the staged programs run to ~100 MB of code each)
FORMAT = 2


def _encode(entry: dict) -> bytes:
    return zlib.compress(
        pickle.dumps(entry, protocol=pickle.HIGHEST_PROTOCOL), 1)


def _decode(blob: bytes) -> dict:
    return pickle.loads(zlib.decompress(blob))

_lock = threading.Lock()
_counts = {"load": 0, "miss": 0, "save": 0, "error": 0}
# one WARN per complaint kind per process (corrupt / identity
# mismatch / unwritable store) — a stale store must not flood boot logs
_warned: set = set()
_fingerprint_memo: list = []
# one record per resolved (kernel, signature); a process resolves a few
# tens (shapes x staged programs), the bound is for a shape explosion
_load_records: deque = deque(maxlen=512)

_M_STORE = GLOBAL_REGISTRY.labeled_counter(
    "aot_store_total",
    "AOT executable-store lookups and writes by outcome "
    "(load|miss|save|error)",
    labelnames=("outcome",))


def _count(outcome: str) -> None:
    with _lock:
        _counts[outcome] += 1
    _M_STORE.labels(outcome=outcome).inc()


def _warn_once(kind: str, message: str) -> None:
    with _lock:
        if kind in _warned:
            return
        _warned.add(kind)
    _LOG.warning("%s", message)


def default_dir() -> str:
    """Repo-adjacent default, next to compilecache's ``.jax_cache``."""
    here = os.path.dirname(os.path.abspath(__file__))
    repo = os.path.dirname(os.path.dirname(here))
    return os.path.join(repo, ".jax_aot")


def store_dir() -> Optional[str]:
    """The resolved store dir, or None when the store is off
    (TEKU_TPU_AOT_STORE=0 or TEKU_TPU_AOT_STORE_DIR=off)."""
    from .env import env_bool
    if not env_bool(ENV_ON, True):
        return None
    configured = env_str(ENV_DIR)
    if configured is not None and configured.lower() in _OFF_VALUES:
        return None
    return configured or default_dir()


def fingerprint() -> str:
    """Hash of the kernel source tree (ops + parallel + the bls
    constants): any edit to the code an executable was traced from
    invalidates the store entry (identity mismatch -> fresh compile),
    so a stale store can never serve an executable whose math the
    tree no longer agrees with."""
    with _lock:
        if _fingerprint_memo:
            return _fingerprint_memo[0]
    here = os.path.dirname(os.path.abspath(__file__))
    pkg = os.path.dirname(here)
    h = hashlib.sha256()
    for rel in ("ops", "parallel"):
        root = os.path.join(pkg, rel)
        for dirpath, _dirs, files in sorted(os.walk(root)):
            for name in sorted(files):
                if not name.endswith(".py"):
                    continue
                path = os.path.join(dirpath, name)
                h.update(os.path.relpath(path, pkg).encode())
                with open(path, "rb") as fh:
                    h.update(fh.read())
    digest = h.hexdigest()[:16]
    with _lock:
        if not _fingerprint_memo:
            _fingerprint_memo.append(digest)
    return _fingerprint_memo[0]


def identity() -> dict:
    """The environment an executable is only valid in: serialized XLA
    programs bind the compiler version and the device they were
    compiled for."""
    import jax
    dev = jax.devices()[0]
    return {"format": FORMAT, "jax": jax.__version__,
            "platform": dev.platform,
            "device_kind": dev.device_kind,
            "device_count": jax.device_count(),
            "fingerprint": fingerprint()}


# the identity keys that say WHERE an entry may run, as against what
# it was built from (format, jax version, the kernels' source)
_PLACE_KEYS = ("platform", "device_kind", "device_count")


def shape_sig(args: Sequence) -> tuple:
    """Canonical hashable signature of one positional-argument tuple:
    the flattened pytree structure plus each leaf's (shape, dtype).
    Works on concrete arrays AND jax.ShapeDtypeStruct avals, so the
    precompiler and the serving wrapper derive the SAME key."""
    import jax
    import numpy as np
    leaves, treedef = jax.tree_util.tree_flatten(tuple(args))
    sig = []
    for leaf in leaves:
        shape = tuple(getattr(leaf, "shape", np.shape(leaf)))
        dtype = getattr(leaf, "dtype", None)
        if dtype is None:
            dtype = np.asarray(leaf).dtype
        sig.append((shape, jax.dtypes.canonicalize_dtype(dtype).name))
    return (str(treedef), tuple(sig))


def entry_key(kernel: str, sig: tuple) -> str:
    """Stable file stem for one (kernel, signature) pair.  The
    identity header is NOT part of the stem: a jax upgrade or code
    edit must find the file and read a MISMATCH (one WARN), not
    silently re-key the store and leak stale blobs forever."""
    h = hashlib.sha256(repr((kernel, sig)).encode()).hexdigest()[:24]
    safe = "".join(c if c.isalnum() or c in "._-" else "_"
                   for c in kernel)[:40]
    return f"{safe}-{h}"


def _entry_path(base: str, kernel: str, sig: tuple) -> str:
    return os.path.join(base, entry_key(kernel, sig) + ".aotx")


def _enforce_cap(base: str) -> None:
    """Evict oldest entries until the store fits the size cap."""
    cap_mb = env_int(ENV_MAX_MB, 2048, lo=1)
    try:
        entries = []
        for name in os.listdir(base):
            if not name.endswith(".aotx"):
                continue
            path = os.path.join(base, name)
            st = os.stat(path)
            entries.append((st.st_mtime, st.st_size, path))
        total = sum(size for _, size, _ in entries)
        entries.sort()
        while total > cap_mb * 1024 * 1024 and entries:
            _mtime, size, path = entries.pop(0)
            os.unlink(path)
            total -= size
            _LOG.info("aot store: evicted %s (size cap %d MB)",
                      os.path.basename(path), cap_mb)
    except OSError as exc:  # pragma: no cover - fs races
        _warn_once("cap", f"aot store: size-cap sweep failed: {exc}")


def save(kernel: str, sig: tuple, compiled) -> Optional[str]:
    """Serialize one compiled executable into the store (atomic
    tmp+rename).  Returns the entry path, or None when the store is
    off or the write failed (one WARN — an unwritable store must cost
    the store, not the precompiler)."""
    base = store_dir()
    if base is None:
        return None
    from jax.experimental import serialize_executable
    try:
        payload, in_tree, out_tree = serialize_executable.serialize(
            compiled)
        # the devices the program executes on, in assignment order: a
        # load must hand them back, or jax loads the executable over
        # EVERY device of the backend and a one-device program then
        # demands one shard per device
        devices = [d.id for d in
                   compiled.runtime_executable().local_devices()]
        blob = _encode(
            {"identity": identity(), "kernel": kernel, "sig": sig,
             "devices": devices,
             "triple": (payload, in_tree, out_tree)})
        os.makedirs(base, exist_ok=True)
        path = _entry_path(base, kernel, sig)
        tmp = path + f".tmp.{os.getpid()}"
        with open(tmp, "wb") as fh:
            fh.write(blob)
        os.replace(tmp, path)
    except Exception as exc:
        _count("error")
        _warn_once("save", f"aot store: write failed ({exc}); "
                           "executables stay process-local")
        return None
    _count("save")
    _enforce_cap(base)
    return path


def load(kernel: str, sig: tuple,
         record: Optional[dict] = None) -> Optional[Callable]:
    """Deserialize the stored executable for (kernel, sig), or None —
    missing entries, and entries built for another platform, device
    count or device assignment, count a miss; corrupt blobs and
    identity mismatches (jax version / code fingerprint) degrade to
    None with ONE WARN per complaint, and the caller compiles fresh.
    `record` (a load record in the making) takes the entry's bytes and
    the seconds of the read and of the deserialize (`decode_s`: its
    first part, decompress + unpickle + identity check, before XLA's
    own deserialize-and-load)."""
    base = store_dir()
    if base is None:
        return None
    path = _entry_path(base, kernel, sig)
    t_read = time.perf_counter()
    try:
        with open(path, "rb") as fh:
            blob = fh.read()
    except OSError:
        _count("miss")
        return None
    t_deser = time.perf_counter()
    if record is not None:
        record["bytes"] = len(blob)
        record["read_s"] = round(t_deser - t_read, 6)
    try:
        return _deserialize(path, blob, record)
    finally:
        if record is not None:
            record["deserialize_s"] = round(
                time.perf_counter() - t_deser, 6)


def _deserialize(path: str, blob: bytes, record: Optional[dict]
                 ) -> Optional[Callable]:
    t_decode = time.perf_counter()
    from jax.experimental import serialize_executable
    try:
        entry = _decode(blob)
        stored = entry["identity"]
    except Exception:
        _count("error")
        _warn_once("corrupt",
                   f"aot store: corrupt entry {os.path.basename(path)}"
                   " (unreadable blob); compiling fresh")
        return None
    import jax
    want = identity()
    by_id = {d.id: d for d in jax.devices()}
    if (any(stored.get(k) != want[k] for k in _PLACE_KEYS)
            or any(i not in by_id for i in entry.get("devices", ()))):
        # built for another platform, device kind or count, or for
        # devices this process lacks: another machine's entry (a
        # checkout that ran the CPU tests, or a one-chip cell, before
        # a four-chip one), not a fault of the store.  A miss: the
        # fresh compile overwrites it for this machine
        _count("miss")
        _LOG.info("aot store: %s was built for %s x%s; compiling for "
                  "this process", os.path.basename(path),
                  stored.get("device_kind"), stored.get("device_count"))
        return None
    if stored != want:
        _count("error")
        drift = sorted(k for k in want
                       if stored.get(k) != want[k])
        _warn_once("identity",
                   "aot store: entries were built for a different "
                   f"environment ({', '.join(drift)} changed); "
                   "compiling fresh — re-run `cli precompile`")
        return None
    if record is not None:
        record["decode_s"] = round(time.perf_counter() - t_decode, 6)
    try:
        payload, in_tree, out_tree = entry["triple"]
        fn = serialize_executable.deserialize_and_load(
            payload, in_tree, out_tree,
            execution_devices=[by_id[i] for i in entry["devices"]])
    except Exception as exc:
        _count("error")
        _warn_once("corrupt",
                   f"aot store: entry {os.path.basename(path)} failed "
                   f"to deserialize ({exc}); compiling fresh")
        return None
    _count("load")
    return fn


def _publish(rec: Optional[dict], t_call: float) -> None:
    """File a load record once its program's first call has returned
    (None: a signature another thread resolved first)."""
    if rec is None:
        return
    rec["first_call_s"] = round(time.perf_counter() - t_call, 6)
    with _lock:
        _load_records.append(rec)


def load_records(since: float = 0.0) -> List[dict]:
    """The load records (copies, oldest first) of the programs resolved
    at or after `since` on the `t_mono` axis."""
    with _lock:
        return [dict(r) for r in _load_records
                if r["t_mono"] >= since]


def stats() -> dict:
    """Process-local store counters (one JSON-able dict)."""
    with _lock:
        return {"dir": store_dir(), "loads": _counts["load"],
                "misses": _counts["miss"], "saves": _counts["save"],
                "errors": _counts["error"]}


def delta(before: dict, after=None) -> dict:
    """Counter movement between two stats() snapshots."""
    if after is None:
        after = stats()
    return {key: after[key] - before[key]
            for key in ("loads", "misses", "saves", "errors")}


class AotDispatcher:
    """The serving seam around one jitted callable.

    Each argument signature resolves ONCE per process: the store
    executable when a valid entry exists, the wrapped jit otherwise —
    after which calls go straight to the resolved callable (the memo
    is the AOT twin of jax's in-memory jit cache).  A store
    executable that fails its FIRST call (an aval corner the signature
    missed, a blob the runtime rejects) permanently falls back to the jit
    for that signature: correctness never depends on the store.

    Every call is a launch of the dispatch that makes it
    (`tracing.launched`), named `program`: the jitted function's name,
    which is its module's on the profiler's line less `jit_` and the
    run id.  A signature's first call is one launch whose seconds hold
    its load or compile."""

    def __init__(self, kernel: str, jit_fn: Callable):
        self.kernel = kernel
        self.program = getattr(jit_fn, "__name__", kernel)
        self._jit = jit_fn
        self._memo: dict = {}
        # signatures whose store executable has not completed a call
        # yet: its first call is the only one that may fall back
        self._unproven: set = set()
        self._memo_lock = threading.Lock()

    def _resolve(self, sig: tuple, args: Sequence
                 ) -> Tuple[Callable, dict]:
        """The callable for `sig` and its load record (`first_call_s`
        is the caller's to fill)."""
        from . import compilecache
        rec = {"kernel": self.kernel, "outcome": "jit", "bytes": 0,
               "read_s": 0.0, "decode_s": 0.0, "deserialize_s": 0.0,
               "compile_s": 0.0, "save_s": 0.0, "first_call_s": None,
               "t_mono": round(clock.mono(), 6)}
        fn = load(self.kernel, sig, rec)
        if fn is not None:
            rec["outcome"] = "aot_load"
            return fn, rec
        if store_dir() is not None:
            # self-populating miss: compile through the explicit AOT
            # path (same XLA work the jit would do, and the persistent
            # compile cache still applies) so the NEXT process loads
            # this signature instead of compiling it
            try:
                before = compilecache.stats()
                t_compile = time.perf_counter()
                compiled = self._jit.lower(*args).compile()
                t_save = time.perf_counter()
                rec["compile_s"] = round(t_save - t_compile, 6)
                # `cache_load` where JAX's persistent cache served the
                # executable, `compile` where XLA did the work
                rec["outcome"] = compilecache.classify_first_dispatch(
                    compilecache.delta(before))
                path = save(self.kernel, sig, compiled)
                rec["save_s"] = round(time.perf_counter() - t_save, 6)
                if path is not None:
                    rec["bytes"] = os.path.getsize(path)
                return compiled, rec
            except Exception as exc:
                _warn_once(f"aotpath:{self.kernel}",
                           f"aot store: {self.kernel} cannot take the "
                           f"AOT lowering path ({exc}); serving from "
                           "jit")
        return self._jit, rec

    def __call__(self, *args):
        return tracing.launched(self.program, self._call, *args)

    def _call(self, *args):
        sig = shape_sig(args)
        with self._memo_lock:
            fn = self._memo.get(sig)
        rec = None
        if fn is None:
            resolved, rec = self._resolve(sig, args)
            with self._memo_lock:
                fn = self._memo.setdefault(sig, resolved)
                if fn is not resolved:
                    rec = None      # another thread resolved it first
                elif fn is not self._jit:
                    self._unproven.add(sig)
        if sig not in self._unproven:
            if rec is None:
                return fn(*args)
            # the jit's own first call of this signature: trace and
            # compile happen inside it, the device run is not waited for
            t_call = time.perf_counter()
            try:
                return fn(*args)
            finally:
                _publish(rec, t_call)
        t_call = time.perf_counter()
        try:
            # wait for the result: a failure of the loaded program may
            # only surface when the device runs it
            import jax
            out = jax.block_until_ready(fn(*args))
        except Exception as exc:
            # a bad blob (devices, avals, a runtime that rejects the
            # executable) costs the store, never the device: serve this
            # signature from the jit from now on
            with self._memo_lock:
                self._memo[sig] = self._jit
                self._unproven.discard(sig)
            _count("error")
            _warn_once(f"callfail:{self.kernel}",
                       f"aot store: {self.kernel} executable failed its "
                       f"first call ({type(exc).__name__}: {exc}); "
                       "serving that signature from jit")
            if rec is not None:
                rec["outcome"] = "jit"
            try:
                return self._jit(*args)
            finally:
                _publish(rec, t_call)
        with self._memo_lock:
            self._unproven.discard(sig)
        _publish(rec, t_call)
        return out

    def precompile(self, avals: Sequence) -> str:
        """Lower + compile this kernel at `avals` and persist it.
        Returns 'load' when the store already held a valid entry,
        else 'compile' (fresh XLA work, now saved)."""
        sig = shape_sig(avals)
        if load(self.kernel, sig) is not None:
            return "load"
        compiled = self._jit.lower(*avals).compile()
        save(self.kernel, sig, compiled)
        return "compile"

    def reset_memo(self) -> None:
        """Test seam: drop resolved signatures so the next call
        re-checks the disk store (a fresh process in miniature)."""
        with self._memo_lock:
            self._memo.clear()
            self._unproven.clear()


_DISPATCHERS: dict = {}
_DISPATCHERS_LOCK = threading.Lock()


def wrap(kernel: str, jit_fn: Callable) -> AotDispatcher:
    """Wrap one jitted callable behind the store (idempotent per
    kernel name — the registry lets tests and the precompiler reach
    every serving dispatcher)."""
    # a jit fn exists, so jax is loaded: install the backend-compile
    # listener NOW, before this kernel's first compile can slip by it
    from . import compilecache
    compilecache.ensure_instrumented()
    with _DISPATCHERS_LOCK:
        disp = _DISPATCHERS.get(kernel)
        if disp is None or disp._jit is not jit_fn:
            disp = AotDispatcher(kernel, jit_fn)
            _DISPATCHERS[kernel] = disp
    return disp


def dispatchers() -> dict:
    """The live kernel-name -> AotDispatcher registry (snapshot)."""
    with _DISPATCHERS_LOCK:
        return dict(_DISPATCHERS)


def reset_memos() -> None:
    """Test seam: make every wrapped kernel re-check the disk store."""
    for disp in dispatchers().values():
        disp.reset_memo()


def _reset_warnings() -> None:
    """Test seam mirroring infra/env.py: re-arm the one-WARN guards."""
    with _lock:
        _warned.clear()

"""Persistent compiled-artifact store — millisecond cold starts.

Restarts are a *routine* event in this stack: the supervisor respawns
gangs on purpose (PR 8), the online loop hot-swaps models continuously
(PR 9), and precision flips redeploy the same architecture (PR 11) —
yet every one of them used to pay live XLA compilation per bucket on
first traffic.  This module extends :mod:`train.step_cache` and the
persistent compilation cache's idea (compiled programs are durable
state, not a per-process accident) into a **versioned artifact store
that travels inside the checkpoint zip**:

- **bake** (deploy/checkpoint time): AOT-lower every (config, bucket,
  precision, kind) program — train step, serve forward, eval — and
  serialize the compiled executable
  (``jax.experimental.serialize_executable``) plus its portable
  StableHLO text (the BASELINE "SameDiff → StableHLO" story) into
  ``artifacts/*`` zip entries next to the weights, indexed by
  ``artifacts/index.json``.  Artifacts ride the PR-4 sha256 manifest,
  so a torn artifact is refused with the rest of the zip.
- **warm** (load time): ``ModelRegistry.deploy``,
  ``Trainer.fit(resume_from=...)``, the supervisor's respawn path and
  ``GatedDeployer`` deserialize matching artifacts into a process-wide
  warm pool *before* taking traffic; the step-cache then hands out
  :class:`WarmedJit` wrappers that dispatch straight to the preloaded
  executable — zero JIT on the request path, zero retraces counted.
- **refuse, never trust**: every index entry records the artifact
  format version, jax version, backend, and the kind's donation
  signature.  Any mismatch — or an undeserializable payload — is a
  *counted* reject (``tpudl_compile_artifact_rejects_total``) that
  falls back to live compilation; a stale artifact can slow a restart,
  never corrupt it.

Key schema (one index entry per program)::

    {"key":  <step-cache key: net class, sha1(conf json), dtype policy,
              [updater sig, sharding sig,] kind>,
     "kind": "train" | "tbptt" | "train_stats" | "eval" | "serve_forward",
     "in_sig":  [[shape, dtype], ...]   # abstract call signature
     "format":  2, "jax": "0.9.0", "backend": "cpu",
     "donation": "0,1,2",               # donate_argnums the kind expects
     "devices": [0],                    # device ids the program runs on
     "exec": "artifacts/<id>.exec",     # serialized XLA executable
     "stablehlo": "artifacts/<id>.stablehlo.mlir"}  # portable module

Metrics: the ``tpudl_compile_*`` family (docs/observability.md).
See docs/serving.md and docs/fault_tolerance.md "Warm restarts".
"""

from __future__ import annotations

import json
import logging
import pickle
import queue
import threading
import time
import zipfile
from typing import Any, Callable, Optional, Sequence

log = logging.getLogger("deeplearning4j_tpu")

ARTIFACT_FORMAT = 2    # 2: entries name the devices they were baked for
INDEX_ENTRY = "artifacts/index.json"

# donate_argnums each program kind is built with (train/trainer.py,
# serve/engine.py).  An artifact whose recorded donation signature
# disagrees was baked by a different build of the step builders — its
# executable would alias (or fail to alias) the wrong buffers, so it is
# refused, never trusted.  Unknown kinds (a future format) are refused
# the same way.
KIND_DONATION = {
    "train": "0,1,2",
    "train_stats": "0,1,2",
    "tbptt": "0,1,2,3",
    "eval": "",
    "serve_forward": "",
}

# ------------------------------------------------------------ process pool
# key string → {call signature → pool item}.  Each item keeps the
# loaded executable (what WarmedJit dispatches to) AND the serialized
# zip entries it came from, so a warmed process can re-embed the same
# artifacts into ITS checkpoints without ever recompiling — a respawned
# gang worker stays bake-free for the programs it resumed with.  The
# generation counter lets WarmedJit instances invalidate their
# per-signature memo when a later warm_from_zip adds programs (deploy
# after build, respawn after a new checkpoint, ...).
_POOL: dict[str, dict[tuple, dict]] = {}
_POOL_GEN = 0
_POOL_LOCK = threading.RLock()


def enabled() -> bool:
    from deeplearning4j_tpu.config import get_config
    return bool(get_config().artifact_store)


def environment() -> dict:
    """The facts a serialized executable is only valid under."""
    import jax
    return {"format": ARTIFACT_FORMAT, "jax": jax.__version__,
            "backend": jax.default_backend()}


def key_str(key: Sequence) -> str:
    return repr(tuple(key))


# dtype object → name memo: call_signature runs per warmed dispatch,
# and str(dtype) per leaf is the expensive part of an otherwise
# C-speed tree flatten.  dtype objects are hashable and few.
_DTYPE_NAMES: dict = {}


def _dtype_name(dtype) -> str:
    name = _DTYPE_NAMES.get(dtype)
    if name is None:
        name = str(dtype)
        if len(_DTYPE_NAMES) < 256:    # paranoia bound, never in practice
            _DTYPE_NAMES[dtype] = name
    return name


def call_signature(args: Any) -> tuple:
    """(shape, dtype) of every array leaf — the dispatch key a warmed
    call is matched on.  Abstract (ShapeDtypeStruct) and concrete
    arrays produce the same signature, so bake-time and call-time sides
    agree; dtypes distinguish an int8-quantized variant from its bf16
    sibling under the same step-cache key.  Runs on the warmed hot path
    (once per dispatch): tree_leaves is C-speed and the dtype names are
    memoized, so the cost is one small tuple build per array leaf."""
    import jax
    return tuple(
        (tuple(leaf.shape),
         _dtype_name(leaf.dtype) if hasattr(leaf, "dtype") else "?")
        for leaf in jax.tree_util.tree_leaves(args)
        if hasattr(leaf, "shape"))


def _sig_to_json(sig: tuple) -> list:
    return [[list(shape), dtype] for shape, dtype in sig]


def _sig_from_json(data: list) -> tuple:
    return tuple((tuple(shape), str(dtype)) for shape, dtype in data)


def clear_pool() -> None:
    """Drop every warmed program (tests ONLY — and never to 'simulate a
    restart' followed by warming the same programs back in: destroying
    a live executable and then running its deserialized twin corrupts
    XLA:CPU internals the two share.  Real restart coverage uses a real
    subprocess; the pool's first-wins insert keeps in-process flows
    away from that sequence by construction)."""
    global _POOL_GEN
    with _POOL_LOCK:
        _POOL.clear()
        _POOL_GEN += 1


def pool_generation() -> int:
    with _POOL_LOCK:
        return _POOL_GEN


def warm_count(key: Optional[Sequence] = None) -> int:
    with _POOL_LOCK:
        if key is not None:
            return len(_POOL.get(key_str(key), {}))
        return sum(len(v) for v in _POOL.values())


def _pool_insert(kstr: str, sig: tuple, compiled: Any,
                 entries: Optional[dict] = None,
                 index_entry: Optional[dict] = None) -> bool:
    """Insert unless an equivalent program is already resident — FIRST
    WINS.  (key, sig) pins the program abstractly (config sha, dtypes,
    shapes, kind); weights are runtime arguments, so a resident twin is
    semantically identical and replacing it would *destroy* a live
    executable the runtime may still share internals with — measured on
    XLA:CPU as heap corruption when a deserialized twin overwrote its
    freshly-baked sibling.  Skipping the overwrite is both the safe and
    the cheap move (no pointless deserialization on redeploys)."""
    global _POOL_GEN
    with _POOL_LOCK:
        table = _POOL.setdefault(kstr, {})
        if sig in table:
            return False
        table[sig] = {"call": compiled, "entries": dict(entries or {}),
                      "index": index_entry}
        _POOL_GEN += 1
        return True


def _pool_has(kstr: str, sig: tuple) -> bool:
    with _POOL_LOCK:
        return sig in _POOL.get(kstr, {})


def _pool_lookup(kstr: str, sig: tuple):
    """(has_any_for_key, loaded_callable_or_None)."""
    with _POOL_LOCK:
        table = _POOL.get(kstr)
        if not table:
            return False, None
        item = table.get(sig)
        return True, (item["call"] if item is not None else None)


def pool_artifact(key: Sequence, sig: tuple):
    """The serialized (entries, index_entry) behind a warmed program,
    when the pool still holds them — lets a bake re-embed an artifact
    it was itself warmed from, without recompiling.  None otherwise."""
    with _POOL_LOCK:
        item = _POOL.get(key_str(key), {}).get(sig)
        if item is None or not item.get("entries") \
                or item.get("index") is None:
            return None
        return dict(item["entries"]), dict(item["index"])


# ------------------------------------------------------------- warm wrapper
class WarmedJit:
    """A jit-wrapped step that answers from the artifact pool first.

    Calls whose (shape, dtype) signature matches a warmed executable
    dispatch straight to it — no trace, no compile, and the inner jit
    cache stays empty so the recompile guards
    (``step_cache.jit_cache_entries``) truthfully report zero.  Any
    other signature falls through to the live jit function (counted as
    an artifact miss when the pool holds programs for this key).
    Attribute access (``lower``, ``_cache_size``, ...) delegates to the
    wrapped function, so the cost model and the recompile guard treat a
    warmed step exactly like a bare one.
    """

    _MISS = object()

    def __init__(self, fn: Any, key: Sequence):
        self._fn = fn
        self._key_str = key_str(key)
        self._memo: dict[tuple, Any] = {}
        self._memo_gen = -1
        self._pool_empty = False
        # signatures actually served from the store (observability)
        self.warm_served: set = set()

    def __call__(self, *args):
        gen = pool_generation()
        if gen != self._memo_gen:
            # a warm load landed (or the pool was cleared): re-resolve
            self._memo = {}
            self._memo_gen = gen
            self._pool_empty = False
        if self._pool_empty:
            return self._fn(*args)
        sig = call_signature(args)
        hit = self._memo.get(sig, self._MISS)
        if hit is self._MISS:
            has_any, hit = _pool_lookup(self._key_str, sig)
            if not has_any:
                # nothing warmed for this program at all — plain live
                # path, not an artifact miss worth counting
                self._pool_empty = True
                return self._fn(*args)
            self._memo[sig] = hit
        from deeplearning4j_tpu.obs.registry import get_registry
        if hit is None:
            get_registry().counter(
                "tpudl_compile_artifact_misses_total").inc()
            return self._fn(*args)
        get_registry().counter("tpudl_compile_artifact_hits_total").inc()
        self.warm_served.add(sig)
        return hit(*args)

    def __getattr__(self, name):
        return getattr(self.__dict__["_fn"], name)


def maybe_wrap(key: Optional[Sequence], fn: Any) -> Any:
    """Wrap a freshly built step in :class:`WarmedJit` when the store is
    enabled and the step has a stable cache key.  Uncacheable configs
    (``key=None``) and non-AOT callables pass through untouched."""
    if key is None or fn is None or isinstance(fn, WarmedJit):
        return fn
    if not hasattr(fn, "lower") or not enabled():
        return fn
    return WarmedJit(fn, key)


# ------------------------------------------------------------------- baking
def bake_program(fn: Any, abstract_args: Any, key: Sequence, kind: str,
                 warm: bool = True) -> tuple[dict, dict]:
    """AOT-lower + compile ``fn`` for ``abstract_args`` and serialize it.
    Returns ``(entries, index_entry)`` where ``entries`` maps zip entry
    names to bytes.  ``warm=True`` also inserts the freshly compiled
    executable into the process pool, so the baker itself never
    compiles the same program live afterwards.  A program the pool was
    already warmed with (this process resumed from it, or an earlier
    round baked it) is re-emitted from its stored bytes — no duplicate
    XLA compile, which is what keeps respawned workers and repeated
    online rounds bake-free."""
    import hashlib

    from jax.experimental.serialize_executable import serialize

    from deeplearning4j_tpu.obs.registry import get_registry
    t0 = time.perf_counter()
    sig = call_signature(abstract_args)
    cached = pool_artifact(key, sig)
    if cached is not None and cached[1].get("kind") == kind \
            and all(cached[1].get(k) == v
                    for k, v in environment().items()):
        return cached
    lowered = fn.lower(*abstract_args)
    try:
        stablehlo = lowered.as_text()
    except Exception:            # portability text is best-effort
        stablehlo = None
    compiled = lowered.compile()
    payload, in_tree, out_tree = serialize(compiled)
    blob = pickle.dumps({"payload": payload, "in_tree": in_tree,
                         "out_tree": out_tree},
                        protocol=pickle.HIGHEST_PROTOCOL)
    digest = hashlib.sha1(
        (key_str(key) + repr(sig)).encode()).hexdigest()[:12]
    art_id = f"{kind}-{digest}"
    entries = {f"artifacts/{art_id}.exec": blob}
    index_entry = {
        "id": art_id, "key": list(key), "kind": kind,
        "in_sig": _sig_to_json(sig),
        "donation": KIND_DONATION.get(kind, ""),
        "devices": [d.id for d in
                    compiled.runtime_executable().local_devices()],
        "exec": f"artifacts/{art_id}.exec",
        **environment(),
    }
    if stablehlo is not None:
        entries[f"artifacts/{art_id}.stablehlo.mlir"] = stablehlo.encode()
        index_entry["stablehlo"] = f"artifacts/{art_id}.stablehlo.mlir"
    if warm:
        _pool_insert(key_str(key), sig, compiled, entries=entries,
                     index_entry=index_entry)
    reg = get_registry()
    reg.counter("tpudl_compile_artifacts_baked_total").inc()
    reg.histogram("tpudl_compile_bake_seconds").observe(
        time.perf_counter() - t0)
    return entries, index_entry


def _serve_feature_struct(net, bucket: int):
    """Abstract request features for one bucket, from the config's
    declared InputType (None when the net declares no input shape —
    serve baking then has nothing static to lower against)."""
    import jax
    import numpy as np
    input_type = getattr(net.conf, "input_type", None)
    if input_type is None:
        # a single-input ComputationGraph declares a list of one
        input_types = getattr(net.conf, "input_types", None) or [None]
        input_type = input_types[0]
    if input_type is None:
        return None
    try:
        shape = input_type.batch_shape(bucket)
    except Exception:
        return None
    return jax.ShapeDtypeStruct(tuple(shape), np.float32)


def bake_serve_artifacts(net, buckets: Sequence[int],
                         warm: bool = True) -> tuple[dict, list]:
    """Bake the serve forward for every bucket (the engine's static
    compile budget), keyed exactly like ``serve.engine`` keys its
    step-cached forward — a quantized net bakes distinct signatures
    (its int8 param dtypes) under the same key."""
    from deeplearning4j_tpu.obs import costmodel
    from deeplearning4j_tpu.serve.engine import (_build_forward,
                                                 _pure_forward_net)
    from deeplearning4j_tpu.train import step_cache
    if not _pure_forward_net(net):
        return {}, []
    sig = step_cache.net_signature(net)
    if sig is None:
        return {}, []
    key = sig + ("serve_forward",)
    fwd = step_cache.get_or_build(key, lambda: _build_forward(net))
    inner = fwd._fn if isinstance(fwd, WarmedJit) else fwd
    params_s = costmodel.abstractify(net.params_)
    state_s = costmodel.abstractify(net.state_)
    entries: dict = {}
    index: list = []
    for bucket in sorted(set(int(b) for b in buckets)):
        x_s = _serve_feature_struct(net, bucket)
        if x_s is None:
            continue
        e, ix = bake_program(inner, (params_s, state_s, x_s, None),
                             key, "serve_forward", warm=warm)
        entries.update(e)
        index.append(ix)
    return entries, index


def _merge_index(old: list, new: list) -> list:
    """Index entries keyed by artifact identity (step-cache key, kind,
    abstract call sig); ``new`` wins on collisions.  The ONE merge both
    the net stash and the zip attach use — the identity key must never
    drift between them."""
    def ident(ix: dict) -> tuple:
        return (json.dumps(ix.get("key")), ix.get("kind"),
                json.dumps(ix.get("in_sig")))

    merged = {ident(ix): ix for ix in old}
    for ix in new:
        merged[ident(ix)] = ix
    return list(merged.values())


def stash_on_net(net, entries: dict, index: list) -> None:
    """Attach baked artifacts to a live net so every later
    ``write_model`` embeds them in the checkpoint zip for free (bytes
    reuse — the programs don't change across checkpoints; only the
    weights do)."""
    if not index:
        return
    merged_entries = dict(getattr(net, "_artifact_entries", None) or {})
    merged_entries.update(entries)
    net._artifact_entries = merged_entries
    net._artifact_index = _merge_index(
        getattr(net, "_artifact_index", None) or [], index)


def zip_entries_for(net) -> dict:
    """The ``artifacts/*`` zip entries for a net (or snapshot) carrying
    a stash; empty when nothing was baked."""
    entries = getattr(net, "_artifact_entries", None)
    index = getattr(net, "_artifact_index", None)
    if not entries or not index:
        return {}
    out = dict(entries)
    out[INDEX_ENTRY] = json.dumps({"format": ARTIFACT_FORMAT,
                                   "programs": index})
    return out


def read_index(path: str) -> list:
    """Index entries of a checkpoint zip's artifact store ([] when the
    zip carries none)."""
    try:
        with zipfile.ZipFile(path, "r") as zf:
            if INDEX_ENTRY not in zf.namelist():
                return []
            data = json.loads(zf.read(INDEX_ENTRY).decode())
    except (OSError, ValueError, KeyError, zipfile.BadZipFile):
        return []
    return list(data.get("programs", []))


def attach_to_zip(path: str, entries: dict, index: list) -> None:
    """Merge baked artifacts into an existing checkpoint zip, rewriting
    it atomically with a fresh manifest (the artifacts become part of
    the PR-4 integrity story: a torn artifact fails verification like a
    torn weight file)."""
    from deeplearning4j_tpu.resilience.checkpoint import (
        MANIFEST_NAME, write_checkpoint_zip)
    if not index:
        return
    existing: dict[str, bytes] = {}
    with zipfile.ZipFile(path, "r") as zf:
        for name in zf.namelist():
            if name != MANIFEST_NAME:
                existing[name] = zf.read(name)
    old_index = []
    if INDEX_ENTRY in existing:
        try:
            old_index = json.loads(existing[INDEX_ENTRY].decode()).get(
                "programs", [])
        except ValueError:
            old_index = []
    existing.update(entries)
    existing[INDEX_ENTRY] = json.dumps(
        {"format": ARTIFACT_FORMAT,
         "programs": _merge_index(old_index, index)})
    write_checkpoint_zip(path, existing)


def ensure_zip_artifacts(path: str, net=None,
                         buckets: Optional[Sequence[int]] = None) -> int:
    """Make sure ``path`` carries serve artifacts for ``buckets`` under
    the current environment; bakes and attaches only what is missing.
    Returns the number of programs baked (0 = the zip was already
    warm).  The pre-flip hook for ``GatedDeployer``: after this, the
    registry's deploy of ``path`` warms instead of compiling, so the
    swap window never JITs."""
    if not enabled():
        return 0
    if net is None:
        from deeplearning4j_tpu.io.model_serializer import restore_model
        net = restore_model(path, load_updater=False)
    if buckets is None:
        from deeplearning4j_tpu.serve.engine import _default_buckets
        buckets = _default_buckets(32)
    env = environment()
    have = set()
    for ix in read_index(path):
        if all(ix.get(k) == v for k, v in env.items()) \
                and ix.get("kind") == "serve_forward":
            have.add(json.dumps(ix.get("in_sig")))
    missing = []
    from deeplearning4j_tpu.obs import costmodel
    params_s = costmodel.abstractify(net.params_)
    state_s = costmodel.abstractify(net.state_)
    for bucket in sorted(set(int(b) for b in buckets)):
        x_s = _serve_feature_struct(net, bucket)
        if x_s is None:
            continue
        sig = call_signature((params_s, state_s, x_s, None))
        if json.dumps(_sig_to_json(sig)) not in have:
            missing.append(bucket)
    if not missing:
        return 0
    entries, index = bake_serve_artifacts(net, missing)
    if index:
        attach_to_zip(path, entries, index)
    return len(index)


# ------------------------------------------------------------------ warming
def _entry_rejects(ix: dict, env: dict) -> Optional[str]:
    """Why this index entry must not be trusted (None = loadable)."""
    for fact in ("format", "jax", "backend"):
        if ix.get(fact) != env[fact]:
            return (f"{fact} mismatch: artifact has {ix.get(fact)!r}, "
                    f"this process is {env[fact]!r}")
    kind = ix.get("kind")
    if kind not in KIND_DONATION:
        return f"unknown program kind {kind!r}"
    if ix.get("donation") != KIND_DONATION[kind]:
        return (f"donation signature mismatch for {kind}: artifact has "
                f"{ix.get('donation')!r}, builders use "
                f"{KIND_DONATION[kind]!r}")
    return None


def warm_from_zip(path: str) -> int:
    """Deserialize every env-compatible artifact in ``path`` into the
    process pool.  Mismatched or undeserializable artifacts are counted
    rejects that fall back to live compilation — never an error.
    Returns the number of programs loaded."""
    import jax
    from jax.experimental.serialize_executable import deserialize_and_load

    from deeplearning4j_tpu.obs import flight_recorder
    from deeplearning4j_tpu.obs.registry import get_registry
    if not enabled():
        return 0
    index = read_index(path)
    if not index:
        return 0
    reg = get_registry()
    env = environment()
    by_id = {d.id: d for d in jax.devices()}
    t0 = time.perf_counter()
    loaded = rejected = resident = 0
    with zipfile.ZipFile(path, "r") as zf:
        names = set(zf.namelist())
        for ix in index:
            reason = _entry_rejects(ix, env)
            if reason is None:
                try:
                    kstr = key_str(tuple(ix["key"]))
                    sig = _sig_from_json(ix["in_sig"])
                    baked_for = [int(i) for i in ix["devices"]]
                except (KeyError, TypeError, ValueError):
                    reason = "malformed index entry"
            if reason is None and not set(baked_for) <= set(by_id):
                reason = (f"baked for devices {baked_for}, this process "
                          f"has {sorted(by_id)}")
            if reason is None and _pool_has(kstr, sig):
                # an equivalent program is already resident (baked or
                # previously warmed) — first wins, nothing to load
                resident += 1
                continue
            if reason is None and ix.get("exec") not in names:
                reason = f"exec entry {ix.get('exec')!r} missing from zip"
            raw = None
            if reason is None:
                try:
                    raw = zf.read(ix["exec"])
                    blob = pickle.loads(raw)
                    # the program runs on the devices it was baked for —
                    # one for a serve forward, the mesh's for a layout
                    # step — not on every device of the process
                    compiled = deserialize_and_load(
                        blob["payload"], blob["in_tree"], blob["out_tree"],
                        execution_devices=[by_id[i] for i in baked_for])
                except Exception as e:
                    reason = f"undeserializable: {type(e).__name__}: {e}"
            if reason is not None:
                rejected += 1
                reg.counter("tpudl_compile_artifact_rejects_total").inc()
                flight_recorder.record(
                    "artifact_reject", program=ix.get("kind"),
                    reason=str(reason)[:200], zip=path.rsplit("/", 1)[-1])
                continue
            # keep the serialized bytes with the loaded program: a
            # bake in this process re-embeds them instead of recompiling
            entries = {ix["exec"]: raw}
            if ix.get("stablehlo") in names:
                entries[ix["stablehlo"]] = zf.read(ix["stablehlo"])
            if _pool_insert(kstr, sig, compiled,
                            entries=entries, index_entry=ix):
                loaded += 1
                reg.counter("tpudl_compile_artifacts_loaded_total").inc()
    reg.histogram("tpudl_compile_warm_load_seconds").observe(
        time.perf_counter() - t0)
    reg.gauge("tpudl_compile_warm_programs").set(warm_count())
    if loaded or rejected or resident:
        flight_recorder.record("artifact_warm", loaded=loaded,
                               rejected=rejected, resident=resident,
                               zip=path.rsplit("/", 1)[-1])
    return loaded


# --------------------------------------------------------- background bakes
# bake_program duplicates a program's XLA compile (seconds of host CPU)
# — never pay that on a step or dispatch path.  Trainers enqueue their
# bake onto ONE daemon worker (the costmodel-analyzer pattern);
# drain_bakes() fences tests and benches.
_BAKE_QUEUE: Any = None
_BAKE_WORKER: Optional[threading.Thread] = None
_BAKE_LOCK = threading.Lock()
_BAKE_PENDING = 0


def _bake_worker_loop(q) -> None:
    global _BAKE_PENDING
    while True:
        job = q.get()
        try:
            job()
        except Exception:
            log.warning("background artifact bake failed", exc_info=True)
        finally:
            with _BAKE_LOCK:
                _BAKE_PENDING -= 1
            q.task_done()


def schedule_bake(job: Callable[[], Any]) -> None:
    """Run ``job`` (a bake closure) on the background bake worker."""
    global _BAKE_QUEUE, _BAKE_WORKER, _BAKE_PENDING
    with _BAKE_LOCK:
        _BAKE_PENDING += 1
        if _BAKE_QUEUE is None:
            _BAKE_QUEUE = queue.Queue()
            _BAKE_WORKER = threading.Thread(
                target=_bake_worker_loop, args=(_BAKE_QUEUE,), daemon=True,
                name="tpudl-artifact-baker")
            _BAKE_WORKER.start()
    _BAKE_QUEUE.put(job)


def drain_bakes(timeout_s: float = 120.0) -> bool:
    """Block until every scheduled bake has run (tests, checkpoint
    flush).  Returns False on timeout."""
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        with _BAKE_LOCK:
            if _BAKE_PENDING == 0:
                return True
        time.sleep(0.01)
    return False

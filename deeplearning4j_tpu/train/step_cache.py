"""Process-level compiled-step cache.

``MultiLayerNetwork.fit`` builds a fresh :class:`~deeplearning4j_tpu.
train.trainer.Trainer` per call, and EarlyStopping re-fits /
``ParallelWrapper`` instances each used to build their own
``jax.jit``-wrapped step — every new wrapper object is a fresh trace +
XLA compile even when the network config, updater, and sharding are
identical.  This module keys the jit-wrapped step functions by

    (net type, sha1(conf.to_json()), dtype policy,
     updater signature, donation/sharding signature, step kind)

so Trainer, ``eval_loss``, EarlyStopping re-fits, and ParallelWrapper
all reuse ONE compiled step per distinct configuration.  The cached
closure captures the *first* net object for that key; reuse is sound
because the forward/loss path is a pure function of ``(params, state,
batch)`` and the key pins every config fact the trace depends on.
Trainers with per-layer updater overrides or frozen layers opt out
(key ``None`` → per-instance build, exactly the old behavior).

jax's **persistent compilation cache** (XLA programs serialized to
disk, surviving process restarts) is placed by the entry points — see
:func:`deeplearning4j_tpu.config.place_compile_cache`.

Metrics: ``tpudl_train_step_cache_hits_total`` /
``tpudl_train_step_cache_misses_total``.
"""

from __future__ import annotations

import hashlib
import json
import threading
from collections import OrderedDict
from typing import Any, Callable, Optional

from deeplearning4j_tpu.obs.registry import get_registry

# Bounded so long-lived processes that churn through many distinct
# configs (hyperparameter sweeps) don't pin every net ever trained:
# least-recently-used entries (and the net objects their closures hold)
# fall out past this many distinct (config, kind) pairs.
MAX_ENTRIES = 128

_CACHE: "OrderedDict[tuple, Any]" = OrderedDict()
_LOCK = threading.Lock()


def net_signature(net) -> Optional[tuple]:
    """Stable identity of everything the traced step closes over on the
    model side: net class, full config json, and the global dtype policy
    (compute/param/output dtypes change the compiled program).  None when
    the config cannot be serialized — the caller then skips caching."""
    conf = getattr(net, "conf", None)
    to_json = getattr(conf, "to_json", None)
    if to_json is None:
        return None
    try:
        conf_sha = hashlib.sha1(to_json().encode()).hexdigest()
    except Exception:
        return None
    import jax.numpy as jnp
    from deeplearning4j_tpu.config import dtype_policy
    pol = dtype_policy()
    # dtype names: a policy restored from a checkpoint holds dtype objects
    # where the default holds scalar types, and both must give one key
    # (or a fresh process never finds the artifacts a resumed one baked)
    return (type(net).__name__, conf_sha,
            *(jnp.dtype(d).name for d in (pol.param_dtype, pol.compute_dtype,
                                          pol.output_dtype)))


def updater_signature(conf) -> Optional[str]:
    """Identity of the optimizer the step closes over (updater config +
    gradient normalization); None when it cannot be serialized."""
    from deeplearning4j_tpu.train import updaters as updater_mod
    updater = getattr(conf, "updater", None)
    try:
        d = updater_mod.to_dict(updater) if updater is not None else None
    except Exception:
        return None
    return json.dumps(
        [d, getattr(conf, "gradient_normalization", None),
         getattr(conf, "gradient_normalization_threshold", None)],
        sort_keys=True, default=repr)


def sharding_signature(shardings) -> str:
    """Flat stable string for a pytree of NamedSharding (the ZeRO-1
    opt-state placement pin baked into the step)."""
    if shardings is None:
        return ""
    import jax
    leaves, treedef = jax.tree_util.tree_flatten(shardings)
    return str(treedef) + "|" + "|".join(str(l) for l in leaves)


def _cost_label(key: tuple) -> str:
    """Human label for the roofline cost breakdown: ``kind:NetClass``
    (key layout: (net class, conf sha, dtypes..., ..., kind))."""
    kind = str(key[-1]) if key else "step"
    cls = str(key[0]) if key else ""
    return f"{kind}:{cls}" if cls else kind


def get_or_build(key: Optional[tuple], builder: Callable[[], Any]) -> Any:
    """Return the cached step for ``key``, building (and caching) it on
    first sight.  ``key=None`` bypasses the cache entirely.

    Every step that passes through here is tagged for the roofline cost
    model (``obs.costmodel``) with its cache-key kind — this is the one
    point every compiled step funnels through, so the per-program cost
    breakdown gets real names (``train:MultiLayerNetwork``, ``eval:...``,
    ``dcn_grad_encode:...``) for free.  It is also where the persistent
    artifact store hooks in: cacheable steps are handed out wrapped in
    :class:`~deeplearning4j_tpu.train.artifact_store.WarmedJit`, so a
    process warmed from a checkpoint's serialized executables answers
    matching calls with zero JIT (see train/artifact_store.py)."""
    from deeplearning4j_tpu.obs import costmodel
    from deeplearning4j_tpu.train import artifact_store
    if key is None:
        return builder()
    reg = get_registry()
    with _LOCK:
        fn = _CACHE.get(key)
        if fn is not None:
            _CACHE.move_to_end(key)
            reg.counter("tpudl_train_step_cache_hits_total").inc()
            return fn
    # build outside the lock: builders only wrap (trace/compile happens
    # at first call), but a slow builder must not serialize other keys
    fn = artifact_store.maybe_wrap(key, builder())
    with _LOCK:
        existing = _CACHE.get(key)
        if existing is not None:
            reg.counter("tpudl_train_step_cache_hits_total").inc()
            return existing
        _CACHE[key] = fn
        reg.counter("tpudl_train_step_cache_misses_total").inc()
        while len(_CACHE) > MAX_ENTRIES:
            _CACHE.popitem(last=False)
    costmodel.tag_program(fn, _cost_label(key))
    return fn


def cache_size() -> int:
    with _LOCK:
        return len(_CACHE)


def clear_step_cache() -> None:
    """Drop every cached step (tests; also frees the net objects the
    cached closures capture)."""
    with _LOCK:
        _CACHE.clear()


def jit_cache_entries(*fns) -> int:
    """Total traced-program count across jit-wrapped callables (None and
    non-jit callables count zero).  The recompile guard's measurement:
    a delta > 0 across a step call means XLA traced a new program."""
    total = 0
    for fn in fns:
        size = getattr(fn, "_cache_size", None)
        if size is None:
            continue
        try:
            total += int(size())
        except Exception:   # AOT internals shifted across jax versions
            continue
    return total

"""Heterogeneous pipeline parallelism with a 1F1B schedule.

Generalizes :mod:`deeplearning4j_tpu.parallel.pipeline` (homogeneous
GPipe) to REAL models (SURVEY §2.7 TP/PP row; VERDICT r3 #4):

  * **per-stage parameter pytrees** — each stage is its own callable +
    its own (arbitrarily shaped) params; stages are dispatched with
    ``lax.switch`` on the device's stage index, so embedding / encoder /
    head stages coexist in one SPMD program;
  * **non-uniform widths** — inter-stage activations are flattened and
    padded to the widest boundary; each stage unpads/reshapes its
    statically known input, computes, and re-pads its output (ppermute
    needs one uniform buffer shape);
  * **1F1B schedule** — the Python-side simulator emits per-tick
    (forward-microbatch, backward-microbatch) tables; backward of
    microbatch m starts as soon as its cotangent exists, so at most
    ``S - s`` activations are ever stashed per stage (vs ALL M under
    autodiff-through-GPipe).  The backward tick RECOMPUTES the stage
    forward from the stashed input (remat), so stash memory is one
    stage-input per in-flight microbatch.

The train step computes the loss on the last stage per microbatch and
seeds the backward immediately — forward, loss, backward, and gradient
accumulation all live in ONE jit program; cotangents ride the reverse
ring ppermute.
"""

from __future__ import annotations

from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P
from deeplearning4j_tpu.parallel.mesh import AXIS_PIPE
from deeplearning4j_tpu.utils.jax_compat import pcast, shard_map


# ------------------------------------------------------------- scheduling
def make_1f1b_schedule(n_stages: int, n_micro: int):
    """Simulate non-interleaved 1F1B (PipeDream-flush).  Returns
    (F, B): int arrays [T, S]; entry = microbatch index or -1 (idle).

    Verifies the single-slot-buffer invariant (an arriving activation /
    cotangent is always consumed before the next one lands) and the
    in-flight bound (stage s stashes ≤ S - s inputs).
    """
    S, M = n_stages, n_micro
    INF = 10 ** 9
    arr_f = [[0] * M if s == 0 else [INF] * M for s in range(S)]
    arr_b = [[INF] * M for s in range(S)]
    f_next, b_next = [0] * S, [0] * S
    F_rows, B_rows = [], []
    t = 0
    while any(b_next[s] < M for s in range(S)) and t < 4 * (S + M):
        F_row, B_row = [-1] * S, [-1] * S
        for s in range(S):
            in_flight = f_next[s] - b_next[s]
            limit = S - s                      # 1F1B in-flight cap
            if (f_next[s] < M and in_flight < limit
                    and arr_f[s][f_next[s]] <= t):
                m = f_next[s]
                F_row[s] = m
                f_next[s] += 1
                if s + 1 < S:
                    arr_f[s + 1][m] = t + 1    # activation arrives next tick
                else:
                    arr_b[s][m] = t + 1        # loss seed ready next tick
            elif b_next[s] < M and arr_b[s][b_next[s]] <= t:
                m = b_next[s]
                B_row[s] = m
                b_next[s] += 1
                if s > 0:
                    arr_b[s - 1][m] = t + 1    # cotangent arrives next tick
        F_rows.append(F_row)
        B_rows.append(B_row)
        t += 1
    assert all(b_next[s] == M for s in range(S)), "schedule did not drain"
    F = np.asarray(F_rows, np.int32)
    B = np.asarray(B_rows, np.int32)
    _verify_single_slot(F, B, S, M)
    return F, B


def _verify_single_slot(F, B, S, M):
    """Every arrival is consumed before the next lands (the scan carries
    one fwd slot and one bwd slot per device)."""
    for s in range(1, S):
        pending = None
        for t in range(F.shape[0]):
            if t > 0 and F[t - 1, s - 1] >= 0:        # arrival from below
                assert pending is None, f"fwd buffer overrun at stage {s}"
                pending = int(F[t - 1, s - 1])
            if F[t, s] >= 0:
                assert pending == int(F[t, s]), "fwd order violated"
                pending = None
    for s in range(S - 1):
        pending = None
        for t in range(B.shape[0]):
            if t > 0 and B[t - 1, s + 1] >= 0:
                assert pending is None, f"bwd buffer overrun at stage {s}"
                pending = int(B[t - 1, s + 1])
            if B[t, s] >= 0:
                assert pending == int(B[t, s]), "bwd order violated"
                pending = None


def make_gpipe_schedule(n_stages: int, n_micro: int):
    """All-forward-then-all-backward schedule in the same table format
    (for memory comparison against 1F1B; stash depth becomes M)."""
    S, M = n_stages, n_micro
    T = S + M - 1
    F = -np.ones((2 * T, S), np.int32)
    B = -np.ones((2 * T, S), np.int32)
    for m in range(M):
        for s in range(S):
            F[m + s, s] = m
    for m in range(M):
        for s in reversed(range(S)):
            B[T + m + (S - 1 - s), s] = m
    return F, B


# ------------------------------------------------------- stage IO padding
def _stage_shapes(stage_fns, stage_params, x_shape, x_dtype):
    """Chain eval_shape through the stages → per-boundary activation
    ShapeDtypeStructs (index i = input of stage i; index S = output)."""
    shapes = [jax.ShapeDtypeStruct(x_shape, x_dtype)]
    for fn, p in zip(stage_fns, stage_params):
        out = jax.eval_shape(fn, p, shapes[-1])
        shapes.append(jax.ShapeDtypeStruct(out.shape, out.dtype))
    return shapes


def _feat_size(shape):
    return int(np.prod(shape[1:])) if len(shape) > 1 else 1


def _pad_to(x, width):
    flat = x.reshape(x.shape[0], -1).astype(jnp.float32)
    return jnp.pad(flat, ((0, 0), (0, width - flat.shape[1])))


def _unpad(buf, shape, dtype):
    n = _feat_size(shape)
    return buf[:, :n].reshape(shape).astype(dtype)


# ---------------------------------------------------------- the train step
def _spec_mentions(spec, axis_name: str) -> bool:
    """True when a PartitionSpec shards any dim over ``axis_name``."""
    for entry in spec:
        if entry is None:
            continue
        entries = entry if isinstance(entry, (tuple, list)) else (entry,)
        if any(str(a) == axis_name for a in entries):
            return True
    return False


def pipeline_train_step(stage_fns: Sequence[Callable], stage_params,
                        x, labels, loss_fn, mesh: Mesh,
                        n_microbatches: int, axis: str = AXIS_PIPE,
                        schedule: str = "1f1b",
                        data_axis: Optional[str] = None,
                        model_axis: Optional[str] = None,
                        rng=None, head_loss: Optional[Callable] = None,
                        param_specs=None, boundary_shapes=None):
    """One pipelined training step over heterogeneous stages.

    - ``stage_fns[i](params_i, h) -> h'``: arbitrary per-stage pytrees
      and activation shapes (batch dim preserved).  With ``rng`` given,
      the convention becomes ``stage_fns[i](params_i, h, rng) -> h'`` —
      the SAME key reaches every stage (fold per layer inside the fn),
      so per-layer dropout reproduces the single-device masks exactly
      when ``n_microbatches == 1``.
    - ``loss_fn(y, labels_mb) -> scalar``: evaluated on the LAST stage
      per microbatch (mean over microbatches is returned).
      Alternatively ``head_loss(params_last, h, labels_mb[, rng])``
      computes the loss FROM the last stage's params and input — the
      hook the unified trainer uses for output layers whose loss needs
      the layer's own parameters (``compute_score_array``); the last
      stage fn is then used only for shape chaining.
    - ``data_axis``: composes DP×PP on one mesh — batch and labels
      shard their leading dim over it, each data replica runs the
      schedule on its shard, and loss/grads pmean across replicas.
    - ``model_axis`` + ``param_specs``: composes TP×PP — parameter
      leaves sharded over ``model_axis`` per ``param_specs`` enter the
      program as local shards; stage fns gather them on use
      (``lax.all_gather``), so activations stay full-width and dropout
      masks match the single-device run.  The all_gather transpose
      reduce-scatters identical per-rank contributions, so sharded
      leaves' grads are renormalized by the axis size here.
    - ``boundary_shapes``: explicit per-stage-input GLOBAL batch shapes
      ``[(B, ...), ...]`` (one per stage).  Required when stage fns
      contain collectives (the eval_shape chain runs outside shard_map
      where mesh axes are unbound); otherwise inferred.
    - returns ``(loss, grads)`` with ``grads`` a tuple of per-stage
      pytrees (cotangents of ``stage_params``), replicated (sharded
      leaves keep their ``param_specs`` layout).

    ``schedule='1f1b'`` bounds stashed activations at ``S - s`` per
    stage; ``'gpipe'`` runs all-fwd-then-all-bwd with an M-deep stash
    (for memory comparison).  Both recompute the stage forward in the
    backward tick (remat), so a stash slot holds one stage INPUT.
    """
    S = int(mesh.shape[axis])
    M = n_microbatches
    dp = int(mesh.shape[data_axis]) if data_axis else 1
    if len(stage_fns) != S:
        raise ValueError(f"{len(stage_fns)} stage fns for {S}-way '{axis}' axis")
    if x.shape[0] % (M * dp):
        raise ValueError(f"batch {x.shape[0]} not divisible by "
                         f"microbatches*data_par={M * dp}")
    bm = x.shape[0] // (M * dp)

    threaded_rng = rng is not None

    def call_stage(i, p, h, r=None):
        return stage_fns[i](p, h, r) if threaded_rng else stage_fns[i](p, h)

    if boundary_shapes is not None:
        if len(boundary_shapes) != S:
            raise ValueError(f"{len(boundary_shapes)} boundary shapes for "
                             f"{S} stages")
        # per-stage INPUT shapes, local microbatch rows; trailing dims
        # come from the declared global shapes
        shapes = [jax.ShapeDtypeStruct((bm,) + tuple(s[1:]), x.dtype)
                  for s in boundary_shapes]
        # the last stage's output never rides the ring (see `width`);
        # close the chain with its input so max() below stays correct
        shapes = shapes + [shapes[-1]]
    else:
        mb_shape = (bm,) + tuple(x.shape[1:])
        if threaded_rng:
            # shape probe outside shard_map: a dummy key stands in (the
            # real key is a same-shape operand at run time)
            key0 = jax.random.key(0)
            probe = [(lambda p, h, _i=i: stage_fns[_i](p, h, key0))
                     for i in range(S)]
            shapes = _stage_shapes(probe, stage_params, mb_shape, x.dtype)
        else:
            shapes = _stage_shapes(stage_fns, stage_params, mb_shape, x.dtype)
    # ring/stash width covers stage INPUT boundaries only: the last
    # stage's forward output (e.g. vocab-wide MLM logits) never rides
    # the ring — its backward tick recomputes it for the loss — so
    # sizing buffers to it would inflate every payload V/H-fold
    width = max(_feat_size(s.shape) for s in shapes[:-1])
    stash_depth = S if schedule == "1f1b" else M

    if schedule == "1f1b":
        F_sched, B_sched = make_1f1b_schedule(S, M)
    elif schedule == "gpipe":
        F_sched, B_sched = make_gpipe_schedule(S, M)
    else:
        raise ValueError(f"unknown schedule {schedule!r}")
    n_ticks = F_sched.shape[0]

    # per-stage wrappers over the padded uniform buffer.  Branch outputs
    # must share one vma type; zeros/constants are made device-varying by
    # deriving them from a varying operand value (NOT lax.pcast inside a
    # branch — a collective-ish annotation inside lax.switch's
    # conditional miscompiles on the CPU backend).
    def fwd_branch(i):
        def run(operand):
            params, buf, r = operand
            if i == S - 1:
                # output never consumed (the B tick recomputes it with
                # the loss attached) — skip the compute entirely
                return jnp.zeros((bm, width), jnp.float32) + buf[0, 0] * 0
            h = _unpad(buf, shapes[i].shape, shapes[i].dtype)
            y = call_stage(i, params[i], h, r)
            return _pad_to(y, width)
        return run

    def bwd_branch(i):
        def run(operand):
            params, in_buf, ct_buf, labels_mb, r = operand
            h = _unpad(in_buf, shapes[i].shape, shapes[i].dtype)
            vzero = jnp.zeros((), jnp.float32) * in_buf[0, 0]  # varying 0

            if i == S - 1:
                if head_loss is not None:
                    def head(p, hh):
                        if threaded_rng:
                            return head_loss(p, hh, labels_mb, r)
                        return head_loss(p, hh, labels_mb)
                else:
                    def head(p, hh):
                        return loss_fn(call_stage(i, p, hh, r), labels_mb)
                loss, (gp, gh) = jax.value_and_grad(
                    head, argnums=(0, 1))(params[i], h)
            else:
                y, vjp = jax.vjp(lambda p, hh: call_stage(i, p, hh, r),
                                 params[i], h)
                ct = _unpad(ct_buf, shapes[i + 1].shape, jnp.float32)
                gp, gh = vjp(ct.astype(y.dtype))
                loss = vzero
            # cotangent flows to stage i-1 (wrt its output = our input)
            zero = tuple(jax.tree_util.tree_map(
                lambda a: jnp.zeros_like(a, dtype=jnp.float32) + vzero, p)
                for p in params)
            grads = tuple(
                jax.tree_util.tree_map(
                    lambda g: g.astype(jnp.float32) + vzero, gp)
                if j == i else zero[j] for j in range(S))
            return _pad_to(gh.astype(jnp.float32), width), grads, loss
        return run

    f_branches = [fwd_branch(i) for i in range(S)]
    b_branches = [bwd_branch(i) for i in range(S)]

    if param_specs is None:
        param_specs = jax.tree_util.tree_map(lambda _: P(),
                                             tuple(stage_params))

    def local(params, x_local, labels_local, *rng_args):
        r = rng_args[0] if rng_args else None
        idx = lax.axis_index(axis)
        micro_x = x_local.reshape((M, bm) + x_local.shape[1:])
        micro_y = labels_local.reshape((M, bm) + labels_local.shape[1:])
        # device-varying zeros built arithmetically from axis_index
        vz = jnp.float32(0.0) * idx
        dv = lambda a: a + vz.astype(a.dtype)
        fwd_buf = dv(jnp.zeros((bm, width), jnp.float32))
        bwd_buf = dv(jnp.zeros((bm, width), jnp.float32))
        stash = dv(jnp.zeros((stash_depth, bm, width), jnp.float32))
        # accumulators mirror the LOCAL argument (sharded leaves arrive
        # as their per-device blocks — zeros_like the closed-over full
        # tree would shape-mismatch them)
        grads0 = jax.tree_util.tree_map(
            lambda a: dv(jnp.zeros_like(a, dtype=jnp.float32)), params)
        loss0 = dv(jnp.float32(0.0))
        fsched = jnp.asarray(F_sched)
        bsched = jnp.asarray(B_sched)

        def tick(carry, t):
            fwd_buf, bwd_buf, stash, grads, loss_acc = carry
            f_mb = fsched[t][idx]
            b_mb = bsched[t][idx]

            # ---- forward op (f_mb >= 0)
            x_in = jnp.where(idx == 0,
                             _pad_to(micro_x[jnp.maximum(f_mb, 0)], width),
                             fwd_buf)
            do_f = f_mb >= 0
            y_out = lax.switch(idx, f_branches, (params, x_in, r))
            stash = stash.at[jnp.maximum(f_mb, 0) % stash_depth].set(
                jnp.where(do_f, x_in, stash[jnp.maximum(f_mb, 0) % stash_depth]))

            # ---- backward op (b_mb >= 0); recomputes fwd from the stash
            slot = jnp.maximum(b_mb, 0) % stash_depth
            gh, gp, mb_loss = lax.switch(
                idx, b_branches,
                (params, stash[slot], bwd_buf, micro_y[jnp.maximum(b_mb, 0)],
                 r))
            do_b = b_mb >= 0
            grads = jax.tree_util.tree_map(
                lambda acc, g: acc + jnp.where(do_b, g.astype(jnp.float32), 0.0),
                grads, gp)
            loss_acc = loss_acc + jnp.where(do_b, mb_loss, 0.0)

            # ---- ring exchange: activations up, cotangents down; only
            # actually-produced payloads overwrite the receiving buffer
            up = [(i, (i + 1) % S) for i in range(S)]
            down = [(i, (i - 1) % S) for i in range(S)]
            sent_f = lax.ppermute(jnp.where(do_f, 1.0, 0.0), axis, up)
            sent_b = lax.ppermute(jnp.where(do_b, 1.0, 0.0), axis, down)
            in_f = lax.ppermute(jnp.where(do_f, y_out, 0.0), axis, up)
            in_b = lax.ppermute(jnp.where(do_b, gh, 0.0), axis, down)
            fwd_buf = jnp.where(sent_f > 0, in_f, fwd_buf)
            bwd_buf = jnp.where(sent_b > 0, in_b, bwd_buf)
            return (fwd_buf, bwd_buf, stash, grads, loss_acc), None

        carry = (fwd_buf, bwd_buf, stash, grads0, loss0)
        (fwd_buf, bwd_buf, stash, grads, loss_acc), _ = lax.scan(
            tick, carry, jnp.arange(n_ticks))
        # each device holds only its own stage's grads (+ last stage the
        # loss); one psum replicates the full tuple everywhere.  Divide
        # by M: returned grads are d(mean-over-microbatch loss)/dp.
        grads = jax.tree_util.tree_map(lambda g: lax.psum(g, axis) / M, grads)
        loss = lax.psum(loss_acc, axis) / M
        if data_axis is not None:
            # DP×PP: each data replica saw an equal-size batch shard —
            # the mean of per-replica means IS the global-batch mean
            grads = jax.tree_util.tree_map(
                lambda g: lax.pmean(g, data_axis), grads)
            loss = lax.pmean(loss, data_axis)
        if model_axis is not None:
            # every model rank ran the identical gathered computation, so
            # the all_gather transpose reduce-scattered `tp` identical
            # contributions into each shard — renormalize sharded leaves
            tp = int(mesh.shape[model_axis])
            grads = jax.tree_util.tree_map(
                lambda g, spec: (g / tp if _spec_mentions(spec, model_axis)
                                 else g),
                grads, param_specs, is_leaf=lambda v: isinstance(v, P))
        return grads, loss

    x_spec = P(data_axis) if data_axis else P()
    # check_vma=False: the schedule switches on axis_index and hands
    # activations round the ring by hand, and under jax 0.9.0 the checked
    # lowering of that program returns WRONG gradients, silently (every
    # element off, up to 0.26 absolute against the autodiff reference in
    # tests/test_pipeline_stages.py::test_train_step_matches_autodiff[1f1b]).
    # The unchecked path lowers switch to a plain local conditional and is
    # what that test pins.
    operands = (tuple(stage_params), x, labels)
    in_specs = (param_specs, x_spec, x_spec)
    if threaded_rng:
        # the key enters as an explicit replicated operand — shard_map
        # cannot close over traced values from an enclosing jit
        operands = operands + (rng,)
        in_specs = in_specs + (P(),)
    grads, loss = shard_map(
        local, mesh=mesh,
        in_specs=in_specs,
        out_specs=(param_specs, P()),
        check_vma=False)(*operands)
    return loss, grads



# ------------------------------------------------- stage-local optimizer
def flatten_stage_params(stage_params):
    """Per-stage pytrees → ([S, Pmax] f32 buffer, unravel fns, sizes).

    The uniform padded buffer is what lets heterogeneous stages live
    STAGE-SHARDED in one SPMD program: shard it ``P(AXIS_PIPE)`` and each
    device holds exactly its own stage's parameters (1/S of the model),
    reconstructing the pytree locally with its static ``unravel``.
    Padding slots are zero and stay zero under any elementwise updater.
    """
    import jax.flatten_util
    flats, unravels, sizes = [], [], []
    for p in stage_params:
        f, u = jax.flatten_util.ravel_pytree(p)
        flats.append(np.asarray(f, np.float32))
        unravels.append(u)
        sizes.append(int(f.size))
    pmax = max(sizes)
    stacked = np.stack([np.pad(f, (0, pmax - f.size)) for f in flats])
    return jnp.asarray(stacked), unravels, sizes


def unflatten_stage_params(params_flat, unravels, sizes):
    """[S, Pmax] buffer → tuple of per-stage pytrees (host-side)."""
    return tuple(u(jnp.asarray(params_flat)[i, :s])
                 for i, (u, s) in enumerate(zip(unravels, sizes)))


def init_stage_local_opt(tx, params_flat, mesh, axis: str = AXIS_PIPE):
    """Optimizer state over the [S, Pmax] buffer, stage-sharded: array
    leaves (mu/nu/momentum — elementwise, param-shaped) shard along the
    stage axis; scalar leaves (step counts) replicate."""
    from jax.sharding import NamedSharding
    opt_state = tx.init(params_flat)
    return jax.tree_util.tree_map(
        lambda a: jax.device_put(
            a, NamedSharding(mesh, P(axis) if np.ndim(a) == 2 else P())),
        opt_state)


def pipeline_fit_step_local(stage_fns: Sequence[Callable], params_flat,
                            opt_state, tx, unravels, sizes,
                            x, labels, loss_fn, mesh: Mesh,
                            n_microbatches: int, axis: str = AXIS_PIPE,
                            schedule: str = "1f1b"):
    """1F1B train step with STAGE-LOCAL gradients and optimizer
    (VERDICT r4 missing #5): no full-tuple psum — the scan carries ONE
    [Pmax] flat gradient per device, and the updater runs inside the
    shard_map on the device's own stage row, so per-device grad + opt
    memory is ≈ 1/S of the model (the memory point of PP at scale;
    SURVEY §2.7 TP/PP row).

    ``params_flat``/``opt_state`` come from :func:`flatten_stage_params`
    / :func:`init_stage_local_opt` and stay sharded ``P(axis)`` across
    steps.  ``tx`` must be an ELEMENTWISE optax chain (sgd/momentum/
    adam/...): cross-parameter transforms (global-norm clipping) would
    see only the local stage's slice.  Only the scalar loss is psum'd.

    Returns ``(loss, new_params_flat, new_opt_state)`` with the same
    shardings as the inputs.
    """
    S = int(mesh.shape[axis])
    M = n_microbatches
    if len(stage_fns) != S:
        raise ValueError(f"{len(stage_fns)} stage fns for {S}-way '{axis}' axis")
    if x.shape[0] % M:
        raise ValueError(f"batch {x.shape[0]} not divisible by {M} microbatches")
    bm = x.shape[0] // M
    pmax = int(params_flat.shape[1])

    # shape chaining needs example pytrees; rebuild from the (host-safe)
    # flat buffer once at trace time
    example_params = unflatten_stage_params(np.zeros((S, pmax), np.float32),
                                            unravels, sizes)
    mb_shape = (bm,) + tuple(x.shape[1:])
    shapes = _stage_shapes(stage_fns, example_params, mb_shape, x.dtype)
    width = max(_feat_size(s.shape) for s in shapes[:-1])
    stash_depth = S if schedule == "1f1b" else M

    if schedule == "1f1b":
        F_sched, B_sched = make_1f1b_schedule(S, M)
    elif schedule == "gpipe":
        F_sched, B_sched = make_gpipe_schedule(S, M)
    else:
        raise ValueError(f"unknown schedule {schedule!r}")
    n_ticks = F_sched.shape[0]

    def stage_tree(i, row):
        return unravels[i](row[:sizes[i]])

    def fwd_branch(i):
        def run(operand):
            row, buf = operand
            if i == S - 1:
                return jnp.zeros((bm, width), jnp.float32) + buf[0, 0] * 0
            h = _unpad(buf, shapes[i].shape, shapes[i].dtype)
            y = stage_fns[i](stage_tree(i, row), h)
            return _pad_to(y, width)
        return run

    def bwd_branch(i):
        def run(operand):
            row, in_buf, ct_buf, labels_mb = operand
            h = _unpad(in_buf, shapes[i].shape, shapes[i].dtype)
            vzero = jnp.zeros((), jnp.float32) * in_buf[0, 0]

            def as_flat(gp):
                import jax.flatten_util
                flat = jax.flatten_util.ravel_pytree(gp)[0].astype(jnp.float32)
                return jnp.pad(flat, (0, pmax - sizes[i]))

            if i == S - 1:
                def head(row_p, hh):
                    return loss_fn(stage_fns[i](stage_tree(i, row_p), hh),
                                   labels_mb)
                loss, (g_row, gh) = jax.value_and_grad(
                    head, argnums=(0, 1))(row, h)
                # grad wrt the padded row is already flat [Pmax]
                return (_pad_to(gh.astype(jnp.float32), width),
                        g_row.astype(jnp.float32), loss)
            y, vjp = jax.vjp(lambda p, hh: stage_fns[i](p, hh),
                             stage_tree(i, row), h)
            ct = _unpad(ct_buf, shapes[i + 1].shape, jnp.float32)
            gp, gh = vjp(ct.astype(y.dtype))
            return (_pad_to(gh.astype(jnp.float32), width),
                    as_flat(gp) + vzero, vzero)
        return run

    f_branches = [fwd_branch(i) for i in range(S)]
    b_branches = [bwd_branch(i) for i in range(S)]

    def local(params_local, opt_local, x_local, labels_local):
        idx = lax.axis_index(axis)
        row = params_local[0]                      # [Pmax] — OUR stage only
        micro_x = x_local.reshape((M, bm) + x_local.shape[1:])
        micro_y = labels_local.reshape((M, bm) + labels_local.shape[1:])
        vz = jnp.float32(0.0) * idx
        dv = lambda a: a + vz.astype(a.dtype)
        fwd_buf = dv(jnp.zeros((bm, width), jnp.float32))
        bwd_buf = dv(jnp.zeros((bm, width), jnp.float32))
        stash = dv(jnp.zeros((stash_depth, bm, width), jnp.float32))
        grads0 = dv(jnp.zeros((pmax,), jnp.float32))   # ONE stage's flat grad
        loss0 = dv(jnp.float32(0.0))
        fsched = jnp.asarray(F_sched)
        bsched = jnp.asarray(B_sched)

        def tick(carry, t):
            fwd_buf, bwd_buf, stash, grads, loss_acc = carry
            f_mb = fsched[t][idx]
            b_mb = bsched[t][idx]
            x_in = jnp.where(idx == 0,
                             _pad_to(micro_x[jnp.maximum(f_mb, 0)], width),
                             fwd_buf)
            do_f = f_mb >= 0
            y_out = lax.switch(idx, f_branches, (row, x_in))
            stash = stash.at[jnp.maximum(f_mb, 0) % stash_depth].set(
                jnp.where(do_f, x_in,
                          stash[jnp.maximum(f_mb, 0) % stash_depth]))

            slot = jnp.maximum(b_mb, 0) % stash_depth
            gh, g_flat, mb_loss = lax.switch(
                idx, b_branches,
                (row, stash[slot], bwd_buf, micro_y[jnp.maximum(b_mb, 0)]))
            do_b = b_mb >= 0
            grads = grads + jnp.where(do_b, g_flat, 0.0)
            loss_acc = loss_acc + jnp.where(do_b, mb_loss, 0.0)

            up = [(i, (i + 1) % S) for i in range(S)]
            down = [(i, (i - 1) % S) for i in range(S)]
            sent_f = lax.ppermute(jnp.where(do_f, 1.0, 0.0), axis, up)
            sent_b = lax.ppermute(jnp.where(do_b, 1.0, 0.0), axis, down)
            in_f = lax.ppermute(jnp.where(do_f, y_out, 0.0), axis, up)
            in_b = lax.ppermute(jnp.where(do_b, gh, 0.0), axis, down)
            fwd_buf = jnp.where(sent_f > 0, in_f, fwd_buf)
            bwd_buf = jnp.where(sent_b > 0, in_b, bwd_buf)
            return (fwd_buf, bwd_buf, stash, grads, loss_acc), None

        carry = (fwd_buf, bwd_buf, stash, grads0, loss0)
        (fwd_buf, bwd_buf, stash, grads, loss_acc), _ = lax.scan(
            tick, carry, jnp.arange(n_ticks))
        grads = grads / M                      # mean over microbatches
        # ONLY the loss crosses devices — grads and opt state stay local
        loss = lax.psum(loss_acc, axis) / M

        opt_row = jax.tree_util.tree_map(
            lambda a: a[0] if a.ndim == 2 else a, opt_local)
        updates, new_opt_row = tx.update(grads, opt_row, row)
        new_row = row + updates
        new_opt = jax.tree_util.tree_map(
            lambda orig, new: new[None] if orig.ndim == 2 else new,
            opt_local, new_opt_row)
        return new_row[None], new_opt, loss

    opt_specs = jax.tree_util.tree_map(
        lambda a: P(axis) if np.ndim(a) == 2 else P(), opt_state)
    new_params, new_opt, loss = shard_map(
        local, mesh=mesh,
        in_specs=(P(axis), opt_specs, P(), P()),
        out_specs=(P(axis), opt_specs, P()),
        check_vma=False)(params_flat, opt_state, x, labels)
    return loss, new_params, new_opt


def pipeline_apply_stages(stage_fns: Sequence[Callable], stage_params,
                          x, mesh: Mesh, n_microbatches: int,
                          axis: str = AXIS_PIPE):
    """Forward-only heterogeneous pipeline (GPipe fill-drain): per-stage
    pytrees + non-uniform widths, same padded-ring machinery as
    :func:`pipeline_train_step`.  Returns y [B, ...] from the last stage.
    """
    S = int(mesh.shape[axis])
    M = n_microbatches
    if len(stage_fns) != S:
        raise ValueError(f"{len(stage_fns)} stage fns for {S}-way '{axis}' axis")
    if x.shape[0] % M:
        raise ValueError(f"batch {x.shape[0]} not divisible by {M} microbatches")
    bm = x.shape[0] // M
    shapes = _stage_shapes(stage_fns, stage_params,
                           (bm,) + tuple(x.shape[1:]), x.dtype)
    width = max(_feat_size(s.shape) for s in shapes)
    out_shape, out_dtype = shapes[-1].shape, shapes[-1].dtype
    n_ticks = S + M - 1

    def fwd_branch(i):
        def run(operand):
            params, buf = operand
            h = _unpad(buf, shapes[i].shape, shapes[i].dtype)
            return _pad_to(stage_fns[i](params[i], h), width)
        return run

    branches = [fwd_branch(i) for i in range(S)]

    def local(params, x_local):
        idx = lax.axis_index(axis)
        micro = x_local.reshape((M, bm) + x_local.shape[1:])
        dv = lambda a: pcast(a, (axis,), to="varying")
        buf = dv(jnp.zeros((bm, width), jnp.float32))
        outs = dv(jnp.zeros((M, bm, width), jnp.float32))

        def tick(carry, t):
            buf, outs = carry
            inject = jnp.where(t < M, t, 0)
            x_in = jnp.where(idx == 0, _pad_to(micro[inject], width), buf)
            y = lax.switch(idx, branches, (params, x_in))
            out_slot = t - (S - 1)
            valid = (idx == S - 1) & (out_slot >= 0) & (out_slot < M)
            slot = jnp.clip(out_slot, 0, M - 1)
            outs = outs.at[slot].set(jnp.where(valid, y, outs[slot]))
            perm = [(i, (i + 1) % S) for i in range(S)]
            buf = lax.ppermute(y, axis, perm)
            return (buf, outs), None

        (buf, outs), _ = lax.scan(tick, (buf, outs), jnp.arange(n_ticks))
        # only the last stage wrote outs → psum broadcasts it
        return lax.psum(outs, axis)

    param_spec = jax.tree_util.tree_map(lambda _: P(), tuple(stage_params))
    y = shard_map(local, mesh=mesh, in_specs=(param_spec, P()),
                  out_specs=P())(tuple(stage_params), x)
    y = y.reshape((M * bm, width))[:, :_feat_size(out_shape)]
    return y.reshape((M * bm,) + tuple(out_shape[1:])).astype(out_dtype)

"""Compiled training steps.

Two execution paths implement the reference's compute→combine→update loop
(dbs.py:228-238):

**Elastic path** — the DBS path. Each logical worker's forward/backward is its
own XLA executable, compiled for that worker's *bucketed* batch shape and
dispatched onto its device; workers sharing a device serialize there
(contention, like the reference's `-gpu 0,0,0,1`), workers on different
devices run concurrently (JAX async dispatch). Per-worker gradients are
weighted per-example (ops/losses.py) so a plain SUM reproduces the
reference's data-share-weighted combine (dbs.py:293-295); the sum + SGD
update runs as ONE fused collective over the mesh — deliberately unlike the
reference's per-parameter allreduce loop (dbs.py:294-300), which would be
poison on ICI (SURVEY §5.8).

**Fused path** — the uniform fast path (dbs off, or a converged uniform plan,
one worker per chip): a single jitted SPMD step via shard_map — local grad,
optional per-worker clip (reference clips before combining, dbs.py:274),
psum, replicated update. No Python dispatch per worker, full XLA fusion.

Both paths produce bitwise-identical update math for the same plan; they
differ only in scheduling.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dynamic_load_balance_distributeddnn_tpu.models import ModelSpec
from dynamic_load_balance_distributeddnn_tpu.obs import scopes
from dynamic_load_balance_distributeddnn_tpu.ops.augment import augment_images, normalize_images
from dynamic_load_balance_distributeddnn_tpu.ops.faultload import synthetic_load
from dynamic_load_balance_distributeddnn_tpu.ops.losses import (
    per_example_cross_entropy,
    per_example_nll,
)
from dynamic_load_balance_distributeddnn_tpu.parallel import wire as wirefmt
from dynamic_load_balance_distributeddnn_tpu.parallel.mesh import shard_map
from dynamic_load_balance_distributeddnn_tpu.train.state import TrainState


def _per_example_loss(
    spec: ModelSpec, outputs: jnp.ndarray, labels: jnp.ndarray, use_pallas: bool = False
) -> jnp.ndarray:
    if spec.output_kind == "log_probs":
        return per_example_nll(outputs, labels)
    if use_pallas:
        from dynamic_load_balance_distributeddnn_tpu.ops.pallas import fused_softmax_xent

        return fused_softmax_xent(outputs, labels)
    return per_example_cross_entropy(outputs, labels)


def _named(name: str, fn: Callable) -> Callable:
    """``fn`` under the name its program is filed under. jit names the HLO
    module after the function, and a profiler event finds its scope through
    the module's name (obs/scopes.py), so no two programs of the library may
    share one (``per_shard`` named six of them)."""
    fn.__name__ = fn.__qualname__ = name
    return fn


class StepLibrary:
    """Builds and caches every executable one model needs.

    jax.jit's own cache handles the per-shape (bucketed batch) and per-device
    specialization of the elastic path; this class just holds the closed-over
    configuration.
    """

    def __init__(
        self,
        spec: ModelSpec,
        mesh: Mesh,
        tx: optax.GradientTransformation,
        mean: Optional[np.ndarray] = None,
        std: Optional[np.ndarray] = None,
        augment: bool = False,
        grad_clip: float = 0.0,
        compute_dtype: Optional[Any] = None,
        use_pallas: bool = False,
        shard_update: bool = False,
        grad_accum: int = 1,
        compress_grads: str = "",
        remat: bool = False,
        grad_comm: str = "flat",
        grad_comm_wire: str = "int8",
        grad_comm_wires: Optional[Tuple[str, ...]] = None,
        zero1_padded: int = 0,
    ):
        self.spec = spec
        self.mesh = mesh
        self.tx = tx
        # Tree gradient collective (ISSUE 12, N-level since ISSUE 17): on a
        # >=2-level topology mesh (parallel/topology.py TopologyTree), the
        # combine reduce-scatters up the tree — fp32 over the innermost
        # (fastest) axis, then one hop per outer level on that hop's wire
        # codec (parallel/wire.py tree_allreduce) with per-hop
        # error-feedback residuals carried in the TrainState — and
        # all-gathers back down. "flat" keeps the one-psum combine (the
        # only choice on a 1-D mesh).
        self.grad_comm = grad_comm
        self.grad_comm_wire = grad_comm_wire
        self.axes = tuple(mesh.axis_names)
        self.hier = grad_comm == "hier" and len(self.axes) >= 2
        if grad_comm == "hier" and len(self.axes) < 2:
            raise ValueError(
                "grad_comm='hier' needs a tree mesh with >= 2 levels "
                "(parallel/mesh.py tree_mesh); the engine resolves the "
                "factorization and falls back to flat when none exists"
            )
        # Per-hop wire codecs, outermost hop first, one per mesh level; the
        # innermost hop is structurally fp32 (it is the reduce-scatter the
        # residual layout assumes error-free). Default: the legacy single
        # grad_comm_wire on the outermost (slowest) hop, fp32 below — the
        # exact PR-12 two-level behaviour on a two-level mesh.
        if grad_comm_wires:
            wires = tuple(grad_comm_wires)
        else:
            wires = (grad_comm_wire,) + ("fp32",) * max(len(self.axes) - 1, 0)
        if self.hier:
            if len(wires) != len(self.axes):
                raise ValueError(
                    f"grad_comm_wires needs one codec per mesh level: got "
                    f"{len(wires)} for axes {self.axes}"
                )
            if wires[-1] != "fp32":
                raise ValueError(
                    "the innermost tree hop must be fp32 (parallel/wire.py "
                    "tree_allreduce carries no residual for it)"
                )
            for w in wires:
                if w not in wirefmt.WIRE_FORMATS:
                    raise ValueError(f"unknown wire codec {w!r}")
        self.grad_comm_wires = wires
        self.mean = mean
        self.std = std
        self.augment = augment
        self.grad_clip = grad_clip
        self.use_pallas = use_pallas
        # bfloat16 mixed precision: params/activations cast for the forward/
        # backward, f32 master weights + f32 loss/grad accumulation
        self.compute_dtype = compute_dtype
        # Cross-replica weight-update sharding (ZeRO-1 analogue, arXiv
        # 2004.13336), generic over optax transforms since PR 13: gradients
        # reduce-scatter into 1/n flat chunks (optionally on the quantized
        # wire, or through the hierarchical ICI/DCN spine), tx.update runs
        # on the chunk against the flat-init sharded opt state
        # (train/state.py shard_optimizer_state), and the update delta
        # all-gathers back. ``zero1_padded`` is the flat padded parameter
        # count the engine computed at state conversion — the opt-state
        # spec and the update math key off it.
        self.shard_update = shard_update
        self.zero1_padded = int(zero1_padded)
        if shard_update and self.zero1_padded <= 0:
            raise ValueError(
                "shard_update needs zero1_padded (the flat padded parameter "
                "count from train/state.py zero1_padded_size)"
            )
        # State donation is DISABLED under the sharded update — a
        # correctness sanction, not a tuning choice: donating a carry that
        # holds the inject_hyperparams opt state miscompiles on XLA:CPU
        # (jax 0.4.37) — the wrapper's pass-through/astype'd hyperparam
        # outputs let the backend alias carry buffers it also donated, and
        # the SECOND invocation of the executable reads freed memory (nan
        # params, then heap corruption at teardown; reproduced
        # deterministically on fused_epoch, graph-shape dependent —
        # optimization_barrier fences moved the miscompile around instead
        # of killing it, so the sanction is categorical: no donated state
        # buffers, no freed-buffer aliasing). Cost: one transient extra
        # copy of params + the 1/n opt chunks per dispatch — the
        # steady-state optimizer memory the feature exists to shrink is
        # unaffected.
        self._state_donate: tuple = () if shard_update else (0,)
        # Micro-batching inside the fused step (lax.scan over batch slices,
        # grads summed before the collective) — exact under per-example
        # weighting; activation memory scales with batch/grad_accum.
        self.grad_accum = max(int(grad_accum), 1)
        # "int8": gradient collective quantized to 8-bit levels with a shared
        # pmax scale and STOCHASTIC rounding (unbiased — no error-feedback
        # state needed), summed in int16 on the wire. Halves collective bytes
        # vs f32 at 127-level precision; opt-in, fused path only.
        self.compress_grads = compress_grads
        # jax.checkpoint on the training forward: activations recomputed in
        # the backward instead of stored — exact same math, HBM for
        # activations traded for ~1/3 more FLOPs (the standard TPU memory
        # lever; lets batch/model scale past activation-memory limits).
        self.remat = remat
        # Optional AOT compile service (runtime/compiler.py), attached by the
        # engine: superstep_cache_size() folds its compiled superstep
        # variants into the compile-once accounting, since service-dispatched
        # supersteps never populate the lazy jit caches.
        self.aot_service = None
        self._build()

    @classmethod
    def zero1_shell(
        cls,
        mesh: Mesh,
        tx: optax.GradientTransformation,
        zero1_padded: int,
        *,
        hier: bool = False,
        wire: str = "fp32",
        wires: Optional[Tuple[str, ...]] = None,
        compress: str = "",
    ) -> "StepLibrary":
        """A minimal library exposing ONLY the ZeRO-1 update spine —
        ``_zero1_update`` + ``_state_spec`` with no model plumbing — for
        the parity tests (tests/test_zero1.py). Owned HERE so the set of
        attributes the spine reads lives next to the spine: drift breaks
        at this factory, not in a test's copy."""
        lib = cls.__new__(cls)
        lib.mesh = mesh
        lib.axes = tuple(mesh.axis_names)
        lib.hier = hier
        lib.tx = tx
        lib.shard_update = True
        lib.zero1_padded = int(zero1_padded)
        lib.compress_grads = compress
        lib.grad_comm_wire = wire
        lib.grad_comm_wires = (
            tuple(wires)
            if wires
            else (wire,) + ("fp32",) * max(len(lib.axes) - 1, 0)
        )
        lib._state_donate = ()
        return lib

    def _apply_train(self, params, x, rng):
        apply = lambda p, xx: self.spec.module.apply(  # noqa: E731
            self._cast_compute(p), xx, train=True, rngs={"dropout": rng}
        )
        if self.remat and not self.spec.own_remat:
            # prevent_cse=False: safe (and recommended) because the remat'd
            # forward only ever runs under jit, including the grad-accum scan
            # body — avoids optimization barriers in the hot loop.
            return jax.checkpoint(apply, prevent_cse=False)(params, x)
        return apply(params, x)

    def _cast_compute(self, tree):
        if self.compute_dtype is None:
            return tree
        dt = self.compute_dtype
        keep = self.spec.f32_leaves  # e.g. a router, whose product stays in float32

        def cast(path, t):
            if not hasattr(t, "dtype") or t.dtype != jnp.float32:
                return t
            if any(k in jax.tree_util.keystr(path) for k in keep):
                return t
            return t.astype(dt)

        return jax.tree_util.tree_map_with_path(cast, tree)

    # ------------------------------------------------------------ input prep

    def _prep_images(self, x_u8: jnp.ndarray, rng: jax.Array, train: bool) -> jnp.ndarray:
        if self.spec.input_kind == "tokens":
            return x_u8
        if self.mean is None:
            return x_u8.astype(jnp.float32)
        if train and self.augment:
            return augment_images(x_u8, rng, self.mean, self.std)
        return normalize_images(x_u8, self.mean, self.std)

    def _clip_local(self, grads, w):
        """The reference clips each worker's LOCAL mean gradient before the
        weighted combine (dbs.py:274). Our local grad is w_r * g_r, so
        unscale -> clip -> rescale."""
        if not self.grad_clip > 0:
            return grads
        with jax.named_scope(scopes.CLIP):
            w_r = jnp.maximum(jnp.sum(w), 1e-12)
            unscaled = jax.tree_util.tree_map(lambda g: g / w_r, grads)
            gnorm = optax.global_norm(unscaled)
            scale = jnp.minimum(1.0, self.grad_clip / jnp.maximum(gnorm, 1e-12))
            return jax.tree_util.tree_map(lambda g: g * scale, grads)

    def _apply_update(self, state: TrainState, grads, **changes) -> TrainState:
        """The replicated optimizer step every path ends in."""
        with jax.named_scope(scopes.UPDATE):
            updates, opt_state = self.tx.update(grads, state.opt_state, state.params)
            params = optax.apply_updates(state.params, updates)
        return state.replace(
            params=params, opt_state=opt_state, step=state.step + 1, **changes
        )

    # ----------------------------------------------------------- elastic path

    def _build(self):
        spec = self.spec

        def local_grads(params, x, y, w, rng, slow_iters, train_prep_rng):
            """Shared forward/backward for one worker's (padded) batch. Last
            of its outputs are the model's own counts (a routed model's
            arrivals; ``None`` for a model without ``train_aux``), which the
            scanned superstep carries out and the per-step paths drop."""
            with jax.named_scope(scopes.AUGMENT):
                x = self._cast_compute(self._prep_images(x, train_prep_rng, train=True))

            def loss_fn(p):
                # the backward pass needs no scope of its own: JAX names it
                # transpose(jvp(forward)), which obs/scopes.py reads as such
                with jax.named_scope(scopes.FORWARD):
                    out = self._apply_train(p, x, rng)
                    # a model with train_aux hands back counts of its own
                    # (a routed model's arrivals) beside its outputs
                    out, counts = out if spec.train_aux else (out, None)
                    losses = _per_example_loss(
                        spec, out.astype(jnp.float32), y, self.use_pallas
                    )
                    mask = (w > 0).astype(jnp.float32)
                    wloss = jnp.sum(losses * w)
                    return wloss, (jnp.sum(losses * mask), jnp.sum(mask), counts)

            (wloss, (loss_sum, count, counts)), grads = jax.value_and_grad(
                loss_fn, has_aux=True
            )(params)
            grads = self._clip_local(grads, w)

            # Straggler injection (fault_mode='compute'): real, unelidable MXU
            # work whose trip count is a traced scalar.
            with jax.named_scope(scopes.INJECT):
                probe = synthetic_load(slow_iters, wloss)
            return grads, wloss, loss_sum, count, probe, counts

        @jax.jit
        def worker_step_first(params, x, y, w, rng, slow_iters):
            g, wloss, loss_sum, count, probe, _ = local_grads(
                params, x, y, w, rng, slow_iters, rng
            )
            acc = jax.tree_util.tree_map(lambda t: t[None], g)
            return acc, (wloss, loss_sum, count, probe)

        @functools.partial(jax.jit, donate_argnums=(1,))
        def worker_step_acc(params, acc, x, y, w, rng, slow_iters):
            g, wloss, loss_sum, count, probe, _ = local_grads(
                params, x, y, w, rng, slow_iters, rng
            )
            acc = jax.tree_util.tree_map(lambda a, t: a + t[None], acc, g)
            return acc, (wloss, loss_sum, count, probe)

        self.worker_step_first = worker_step_first
        self.worker_step_acc = worker_step_acc
        # shared forward/backward closure, reused by the windowed and
        # superstep executables built lazily below
        self._local_grads = local_grads

        # Windowed twins: the whole staged window rides in once per window and
        # each call slices its step ON DEVICE (lax.dynamic_index_in_dim on a
        # traced step index), so a worker-step dispatch is ONE executable call
        # instead of one call plus 4 host-issued slice dispatches. The jit
        # cache specializes per (window length, bucketed batch) — the
        # superstep cache key of ISSUE 2 — and per device via the committed
        # inputs. Math after the slice is byte-for-byte local_grads.
        def _win_slice(s, *arrays):
            return tuple(
                jax.lax.dynamic_index_in_dim(a, s, 0, keepdims=False)
                for a in arrays
            )

        @jax.jit
        def worker_step_first_win(params, xw, yw, ww, kw, s, slow_iters):
            x, y, w, rng = _win_slice(s, xw, yw, ww, kw)
            g, wloss, loss_sum, count, probe, _ = local_grads(
                params, x, y, w, rng, slow_iters, rng
            )
            acc = jax.tree_util.tree_map(lambda t: t[None], g)
            return acc, (wloss, loss_sum, count, probe)

        @functools.partial(jax.jit, donate_argnums=(1,))
        def worker_step_acc_win(params, acc, xw, yw, ww, kw, s, slow_iters):
            x, y, w, rng = _win_slice(s, xw, yw, ww, kw)
            g, wloss, loss_sum, count, probe, _ = local_grads(
                params, x, y, w, rng, slow_iters, rng
            )
            acc = jax.tree_util.tree_map(lambda a, t: a + t[None], acc, g)
            return acc, (wloss, loss_sum, count, probe)

        @jax.jit
        def worker_step_first_win_idx(
            params, train_x, train_y, iw, ww, kw, s, slow_iters
        ):
            idx, w, rng = _win_slice(s, iw, ww, kw)
            x = jnp.take(train_x, idx, axis=0, mode="clip")
            y = jnp.take(train_y, idx, axis=0, mode="clip")
            g, wloss, loss_sum, count, probe, _ = local_grads(
                params, x, y, w, rng, slow_iters, rng
            )
            acc = jax.tree_util.tree_map(lambda t: t[None], g)
            return acc, (wloss, loss_sum, count, probe)

        @functools.partial(jax.jit, donate_argnums=(1,))
        def worker_step_acc_win_idx(
            params, acc, train_x, train_y, iw, ww, kw, s, slow_iters
        ):
            idx, w, rng = _win_slice(s, iw, ww, kw)
            x = jnp.take(train_x, idx, axis=0, mode="clip")
            y = jnp.take(train_y, idx, axis=0, mode="clip")
            g, wloss, loss_sum, count, probe, _ = local_grads(
                params, x, y, w, rng, slow_iters, rng
            )
            acc = jax.tree_util.tree_map(lambda a, t: a + t[None], acc, g)
            return acc, (wloss, loss_sum, count, probe)

        self.worker_step_first_win = worker_step_first_win
        self.worker_step_acc_win = worker_step_acc_win
        self.worker_step_first_win_idx = worker_step_first_win_idx
        self.worker_step_acc_win_idx = worker_step_acc_win_idx

        # Index-fed twins for the device-resident data cache: the train
        # arrays live in HBM; each step gathers its rows on device, so the
        # host sends [b_pad] int32 indices instead of the batch itself.
        # Padding slots index row 0 and carry weight 0 — identical math to
        # the materialized path (same rows, same weights).
        @jax.jit
        def worker_step_first_idx(params, train_x, train_y, idx, w, rng, slow_iters):
            x = jnp.take(train_x, idx, axis=0, mode="clip")
            y = jnp.take(train_y, idx, axis=0, mode="clip")
            g, wloss, loss_sum, count, probe, _ = local_grads(
                params, x, y, w, rng, slow_iters, rng
            )
            acc = jax.tree_util.tree_map(lambda t: t[None], g)
            return acc, (wloss, loss_sum, count, probe)

        @functools.partial(jax.jit, donate_argnums=(1,))
        def worker_step_acc_idx(params, acc, train_x, train_y, idx, w, rng, slow_iters):
            x = jnp.take(train_x, idx, axis=0, mode="clip")
            y = jnp.take(train_y, idx, axis=0, mode="clip")
            g, wloss, loss_sum, count, probe, _ = local_grads(
                params, x, y, w, rng, slow_iters, rng
            )
            acc = jax.tree_util.tree_map(lambda a, t: a + t[None], acc, g)
            return acc, (wloss, loss_sum, count, probe)

        self.worker_step_first_idx = worker_step_first_idx
        self.worker_step_acc_idx = worker_step_acc_idx

        # -------------------------------------------------- combine + update

        replicated = NamedSharding(self.mesh, P())

        def sum_stacked(stacked_grads):
            with jax.named_scope(scopes.COMBINE):
                return jax.tree_util.tree_map(lambda g: jnp.sum(g, axis=0), stacked_grads)

        @functools.partial(
            jax.jit,
            donate_argnums=(0, 1),
            out_shardings=replicated,
        )
        def combine_update(state: TrainState, stacked_grads):
            return self._apply_update(state, sum_stacked(stacked_grads))

        self.combine_update = combine_update

        # Non-donating twin used for timing probes: same collective + update
        # math, but inputs stay valid and the result is discarded, so probing
        # never double-applies an optimizer step.
        @functools.partial(jax.jit, out_shardings=replicated)
        def combine_probe(state: TrainState, stacked_grads):
            return self._apply_update(state, sum_stacked(stacked_grads))

        self.combine_probe = combine_probe

    # -------------------------------------------------- elastic superstep
    # (engine._train_epoch_elastic, ISSUE 2). One dispatch per WINDOW for a
    # whole device group: a lax.scan over the window's steps whose body
    # replays the per-step path's exact op sequence — each worker's
    # local_grads at its true bucketed shape, the [1,...]-stacked left-fold
    # accumulation, sum over the stacked axis, tx.update, apply — so the
    # result is bitwise-identical to per-step dispatch. Only valid when the
    # group spans EVERY worker (single-device topologies): with workers on
    # several devices, step k's gradients need step k-1's cross-device
    # combine, which no single-device scan can contain.

    def _superstep_body(self, state: TrainState, xs, ys, ws_, ks, slows):
        """One scanned step for a whole worker group: tuples hold one entry
        per worker, each at its own (static) bucketed shape."""
        acc = None
        aux = []
        for i in range(len(ws_)):
            x = xs[i]
            if self.spec.serial_workers and acc is not None:
                # this worker's step waits for the last one's gradient (see
                # ModelSpec.serial_workers)
                x, acc = jax.lax.optimization_barrier((x, acc))
            g, wloss, loss_sum, count, probe, counts = self._local_grads(
                state.params, x, ys[i], ws_[i], ks[i], slows[i], ks[i]
            )
            with jax.named_scope(scopes.COMBINE):
                if acc is None:
                    acc = jax.tree_util.tree_map(lambda t: t[None], g)
                else:
                    acc = jax.tree_util.tree_map(lambda a, t: a + t[None], acc, g)
            row = jnp.stack([wloss, loss_sum, count, probe])
            if counts is not None:
                # [wloss, loss_sum, count, probe, the model's counts...]
                row = jnp.concatenate([row, counts.reshape(-1)])
            aux.append(row)
        with jax.named_scope(scopes.COMBINE):
            grads = jax.tree_util.tree_map(lambda t: jnp.sum(t, axis=0), acc)
        if self.shard_update:
            # ZeRO-1 inside the scan (the shard_update x scan-mode gap,
            # carried since PR 13): scan mode only exists on a 1-device
            # mesh, where the windowed zero-1 combine twin's collectives
            # are identities — with_comm=False with local_index=0 replays
            # the exact same chunk math (chunk == padded, off == 0) with
            # no collective-axis context needed, and the rng recipe
            # matches _sharded_combine_body's at axis index 0.
            rng = jax.random.fold_in(
                jax.random.fold_in(jax.random.PRNGKey(0x5D1E), 0), state.step
            )
            state = self._zero1_update(
                state, grads, rng, with_comm=False, local_index=0
            )
        else:
            state = self._apply_update(state, grads)
        return state, jnp.stack(aux)

    @functools.cached_property
    def group_superstep(self):
        """Materialized-feed superstep: carry = the full TrainState (the
        per-step combine cadence lives INSIDE the scan); scanned inputs are
        per-worker (x, y, w) windows plus the per-step rng keys — the same
        wkeys table the per-step path consumes, so the rng stream is
        identical. Returns (state, aux[win, n_workers, 4])."""

        def superstep(state, xs, ys, ws_, ks, slows):
            def body(st, inp):
                return self._superstep_body(st, *inp, slows)

            # unroll=True: a rolled scan lowers to a while-loop whose body
            # XLA emits with different reduction blocking than the
            # standalone executables — measurably (~1e-8) off the per-step
            # path. Fully unrolled, the window compiles to the same op
            # sequence and the bitwise-parity contract holds; the engine
            # bounds the unroll length via config.superstep_window.
            return jax.lax.scan(body, state, (xs, ys, ws_, ks), unroll=True)

        # donation rides the shard_update sanction (see _state_donate)
        return jax.jit(
            _named("group_superstep", superstep), donate_argnums=self._state_donate
        )

    @functools.cached_property
    def group_superstep_idx(self):
        """Device-cache-fed superstep: the HBM-resident train arrays ride in
        whole (no re-transfer) and each scanned step gathers each worker's
        rows by index on device — the host ships [win, b_pad] int32 per
        worker instead of the batches."""

        def superstep(state, train_x, train_y, idxs, ws_, ks, slows):
            def body(st, inp):
                iw, ws_s, ks_s = inp
                xs = tuple(
                    jnp.take(train_x, i, axis=0, mode="clip") for i in iw
                )
                ys = tuple(
                    jnp.take(train_y, i, axis=0, mode="clip") for i in iw
                )
                return self._superstep_body(st, xs, ys, ws_s, ks_s, slows)

            # unroll=True: see group_superstep — bitwise parity requires the
            # unrolled lowering
            return jax.lax.scan(body, state, (idxs, ws_, ks), unroll=True)

        # donation rides the shard_update sanction (see _state_donate)
        return jax.jit(
            _named("group_superstep_idx", superstep), donate_argnums=self._state_donate
        )

    def superstep_cache_size(self) -> int:
        """Compiled (shape-tuple, window-length) superstep variants — the
        quantity the compile-once contract (tests/test_superstep.py) bounds.
        Counts both lazy-jit cache entries and AOT-service executables (the
        service dispatch path never touches the jit caches)."""
        n = 0
        for name in ("group_superstep", "group_superstep_idx"):
            fn = self.__dict__.get(name)
            if fn is not None:
                n += fn._cache_size()
        if self.aot_service is not None:
            n += self.aot_service.count_keys(("group_superstep",))
        return n

    # --------------------------------------- sharded-state combine twins
    # (elastic dispatch, ISSUEs 12/13): drop-in replacements for
    # combine_update / combine_probe when the combine itself must run
    # inside a shard_map body — the two-level hier spine, and/or the
    # ZeRO-1 sharded update (whose opt-state chunks and reduce-scatter are
    # per-device by construction). Each device sums its own [1, ...] slice
    # of the stacked partials, then the body routes: sharded update when
    # shard_update is on (the zero-1 math internally rides the hier spine
    # or the quantized flat wire as configured), else the hier
    # reduce-scatter / compressed-DCN-hop / all-gather plus the replicated
    # update — with the error-feedback residual carried through the
    # TrainState either way.

    def _sharded_combine_body(self, state: TrainState, stacked):
        with jax.named_scope(scopes.COMBINE):
            local = jax.tree_util.tree_map(lambda g: jnp.sum(g, axis=0), stacked)
        rng = jax.random.fold_in(
            jax.random.fold_in(
                jax.random.PRNGKey(0x5D1E), self._data_axis_index()
            ),
            state.step,
        )
        if self.shard_update:
            return self._zero1_update(state, local, rng, with_comm=True)
        grads, new_residual = self._hier_combine(
            local, rng, state.comm_residual
        )
        return self._apply_update(state, grads, comm_residual=new_residual)

    def _sharded_combine_twin(self, name: str, donate: bool):
        def body(state, stacked):
            return self._sharded_combine_body(state, stacked)

        sharded = shard_map(
            _named(name, body),
            mesh=self.mesh,
            in_specs=(self._state_spec(), P(self._batch_entry)),
            out_specs=self._state_spec(),
            check_vma=False,
        )
        if donate:
            # the stacked partials (argnum 1) always donate; the state only
            # donates on the replicated-update (hier) twins — see the
            # _state_donate sanction in __init__
            return jax.jit(
                sharded, donate_argnums=self._state_donate + (1,)
            )
        return jax.jit(sharded)

    @functools.cached_property
    def combine_update_hier(self):
        return self._sharded_combine_twin("combine_update_hier", donate=True)

    @functools.cached_property
    def combine_probe_hier(self):
        """Non-donating twin for timing probes (inputs stay valid, result —
        including the would-be residual update — is discarded)."""
        return self._sharded_combine_twin("combine_probe_hier", donate=False)

    @functools.cached_property
    def combine_update_zero1(self):
        """Flat-mesh ZeRO-1 combine twin (shard_update without hier): the
        same shard_map spine as the hier twins, with the body routed into
        the sharded update."""
        return self._sharded_combine_twin("combine_update_zero1", donate=True)

    @functools.cached_property
    def combine_probe_zero1(self):
        return self._sharded_combine_twin("combine_probe_zero1", donate=False)

    # ------------------------------------------------------- AOT lowerables
    # The executable families the async compile service can pre-compile,
    # keyed by the names the engine uses in its service keys. Since ISSUE 5
    # the MESH-sharded programs are included too: the fused whole-epoch
    # scans (``fused_epoch``/``fused_epoch_idx``) and the combine twins
    # lower from ShapeDtypeStructs carrying explicit NamedShardings, so
    # warm-start AOT-submits them instead of paying their compile lazily
    # inside the excluded epoch 0 (the PR-3 single-host-probe gate, lifted).
    # Only the fused sync/FLOPs PROBES stay compile_now-with-concrete-args
    # (their input shardings derive from window indexing and are easiest to
    # match from the live arrays).

    def aot_lowerables(self) -> Dict[str, Callable]:
        out = {}
        if self.hier:
            # hier combine twins exist only on a tree mesh (>= 2 levels —
            # building them on a flat mesh would trace collectives over
            # axes the mesh does not define); with shard_update on they
            # ARE the sharded-update twins (the body routes)
            out["combine_update_hier"] = self.combine_update_hier
            out["combine_probe_hier"] = self.combine_probe_hier
        elif self.shard_update:
            out["combine_update_zero1"] = self.combine_update_zero1
            out["combine_probe_zero1"] = self.combine_probe_zero1
        out.update(self._aot_lowerables_base())
        return out

    def _aot_lowerables_base(self) -> Dict[str, Callable]:
        return {
            "worker_first": self.worker_step_first,
            "worker_acc": self.worker_step_acc,
            "worker_first_idx": self.worker_step_first_idx,
            "worker_acc_idx": self.worker_step_acc_idx,
            "worker_first_win": self.worker_step_first_win,
            "worker_acc_win": self.worker_step_acc_win,
            "worker_first_win_idx": self.worker_step_first_win_idx,
            "worker_acc_win_idx": self.worker_step_acc_win_idx,
            "group_superstep": self.group_superstep,
            "group_superstep_idx": self.group_superstep_idx,
            "fused_epoch": self.fused_epoch,
            "fused_epoch_idx": self.fused_epoch_idx,
            "combine_update": self.combine_update,
            "combine_probe": self.combine_probe,
        }

    # ------------------------------------------------------------ fused path
    # (evaluation is always the sharded fused_eval_step — there is no
    # single-device eval path)

    # -------------------------------------------------- mesh-axis plumbing
    # The mesh is 1-D ("data") on flat runs and an N-level topology tree
    # (outermost axis first) when the tree combine resolved. Every
    # collective/spec in the fused bodies routes through these helpers so
    # one code path serves every factorization — on a flat mesh each
    # helper degenerates to exactly the pre-hier spelling (same axis
    # string, same lowering, bitwise-same programs).

    @property
    def _axis_arg(self):
        """Collective axis argument — the lone axis name, or the axis tuple
        (jax.lax collectives reduce over every named axis). ONE source of
        truth with the engine's placement specs: parallel/mesh.py
        ``mesh_batch_axes`` — collectives and batch sharding diverging on
        which axes "the whole mesh" means would reduce gradients over a
        different axis set than the data is sharded on."""
        from dynamic_load_balance_distributeddnn_tpu.parallel.mesh import (
            mesh_batch_axes,
        )

        return mesh_batch_axes(self.mesh)

    @property
    def _batch_entry(self):
        """PartitionSpec entry splitting a batch dim over the whole mesh —
        the same value as :attr:`_axis_arg` (P treats a tuple entry as one
        dim split over all named axes); kept as its own name so spec sites
        read as sharding, collective sites as reduction."""
        return self._axis_arg

    def _data_axis_index(self):
        """Flat device position inside a shard_map body: the mixed-radix
        fold of the per-axis indices, outermost axis most significant —
        identical numbering under EVERY factorization (tree_mesh reshapes
        row-major), so per-device rng folds are invariant to the mesh
        shape."""
        if len(self.axes) == 1:
            return jax.lax.axis_index(self.axes[0])
        idx = jax.lax.axis_index(self.axes[0])
        for a in self.axes[1:]:
            idx = idx * int(self.mesh.shape[a]) + jax.lax.axis_index(a)
        return idx

    # ------------------------------------------- tree gradient combine
    # (ISSUE 12, N-level since ISSUE 17, after DynamiQ's compressed
    # multi-hop all-reduce): reduce-scatter UP the topology tree — fp32
    # over the innermost (fastest) axis, then one hop per outer level on
    # that hop's wire codec, shrinking the vector by the level size each
    # hop — and all-gather back DOWN. Per-hop error-feedback residuals
    # (TrainState.comm_residual) make the biased wires convergent
    # (parallel/wire.py).

    def _hier_combine(self, grads, rng, residual):
        """N-level tree gradient reduction inside a shard_map body.

        ``grads``: this device's local gradient tree. ``residual``: this
        device's per-hop error-feedback rows — a tuple with one [1, W_i]
        slice of ``TrainState.comm_residual`` per hop 0..k-1, outermost
        first. Returns ``(reduced grads tree, new residual tuple)``. The
        tree is raveled ONCE so the whole combine is 2k+1 collectives
        regardless of leaf count (the flat combine pays one psum per
        leaf); the spine itself lives in parallel/wire.py so
        tests/test_grad_comm.py drives the identical code."""
        names = self.axes
        sizes = tuple(int(self.mesh.shape[a]) for a in names)
        with jax.named_scope(scopes.COMBINE):
            out, new_residual = wirefmt.tree_allreduce(
                grads,
                rng,
                names,
                sizes,
                self.grad_comm_wires,
                residuals=(
                    tuple(r[0] for r in residual) if residual is not None else None
                ),
            )
        return out, tuple(r[None] for r in new_residual)

    @functools.cached_property
    def _opt_state_spec(self):
        """Per-leaf shard_map spec pytree of the GENERIC flat-init sharded
        optimizer state (train/state.py shard_optimizer_state): leaves
        whose leading dim is the padded flat parameter count are the 1/n
        chunks (split over the zero-1 chunk axes — device-major on a
        two-level mesh), everything else (inject_hyperparams' lr, adam's
        count) is replicated. Derived from ``tx.init``'s abstract shapes so
        arbitrary optax transforms spec themselves."""
        from dynamic_load_balance_distributeddnn_tpu.parallel.mesh import (
            zero1_chunk_axes,
        )

        padded = self.zero1_padded
        ax = zero1_chunk_axes(self.mesh)
        abs_state = jax.eval_shape(
            self.tx.init, jax.ShapeDtypeStruct((padded,), jnp.float32)
        )
        return jax.tree_util.tree_map(
            lambda l: P(ax) if (l.ndim >= 1 and l.shape[0] == padded) else P(),
            abs_state,
        )

    def _state_spec(self):
        """shard_map spec for the TrainState: fully replicated, except the
        flat 1/n optimizer chunks when weight-update sharding is on
        (prefix-spec pytree: ``params=P()`` covers the whole params
        subtree) and the per-device error-feedback residual on
        hierarchical runs."""
        from dynamic_load_balance_distributeddnn_tpu.train.state import (
            TrainState as TS,
        )

        if self.shard_update:
            return TS(
                params=P(),
                opt_state=self._opt_state_spec,
                step=P(),
                comm_residual=P(self._batch_entry) if self.hier else P(),
            )
        if self.hier:
            return TS(
                params=P(),
                opt_state=P(),
                step=P(),
                comm_residual=P(self._batch_entry),
            )
        return P()

    def _fused_shard_body(self, state, x, y, w, slow_scalar, seed, with_comm=True):
        """Per-device body of the fused SPMD step: local grad, optional
        per-worker clip (reference clips before combining, dbs.py:274), psum,
        replicated SGD update.

        ``with_comm=False`` builds the comm-free twin used by the sync-time
        probe (engine._probe_fused_sync): identical math except the psums are
        skipped, so (t_full − t_nocomm) isolates the collective cost — the
        fused-path analogue of the reference's per-step allreduce wait meter
        (dbs.py:297-299)."""
        spec = self.spec
        idx = self._data_axis_index()
        rng = jax.random.fold_in(
            jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(0), seed), idx),
            state.step,
        )

        def slice_grads(x_s, y_s, w_s, rng_s):
            """Weighted loss + grads for one (micro-)batch slice. Per-example
            weighting makes accumulation exact: sums of weighted slice grads
            equal the whole-batch weighted grad."""
            with jax.named_scope(scopes.AUGMENT):
                x_p = self._cast_compute(self._prep_images(x_s, rng_s, train=True))

            def loss_fn(p):
                with jax.named_scope(scopes.FORWARD):  # see local_grads
                    out = self._apply_train(p, x_p, rng_s)
                    losses = _per_example_loss(
                        spec, out.astype(jnp.float32), y_s, self.use_pallas
                    )
                    mask = (w_s > 0).astype(jnp.float32)
                    return jnp.sum(losses * w_s), (jnp.sum(losses * mask), jnp.sum(mask))

            return jax.value_and_grad(loss_fn, has_aux=True)(state.params)

        acc = self.grad_accum
        if acc > 1:
            b = x.shape[0]
            assert b % acc == 0, (
                f"per-device batch {b} must divide by grad_accum {acc}"
            )

            def micro(carry, inp):
                g_acc, wl, ls, cnt, i = carry
                x_s, y_s, w_s = inp
                (wl_s, (ls_s, cnt_s)), g = slice_grads(
                    x_s, y_s, w_s, jax.random.fold_in(rng, i)
                )
                g_acc = jax.tree_util.tree_map(jnp.add, g_acc, g)
                return (g_acc, wl + wl_s, ls + ls_s, cnt + cnt_s, i + 1), None

            zeros = jax.tree_util.tree_map(jnp.zeros_like, state.params)
            stacked = (
                x.reshape((acc, b // acc) + x.shape[1:]),
                y.reshape((acc, b // acc) + y.shape[1:]),
                w.reshape((acc, b // acc) + w.shape[1:]),
            )
            (grads, wloss, loss_sum, count, _), _ = jax.lax.scan(
                micro,
                (zeros, jnp.float32(0), jnp.float32(0), jnp.float32(0), jnp.int32(0)),
                stacked,
            )
        else:
            (wloss, (loss_sum, count)), grads = slice_grads(x, y, w, rng)
        grads = self._clip_local(grads, w)

        with jax.named_scope(scopes.INJECT):
            probe = synthetic_load(slow_scalar, wloss)
        metrics = jnp.stack([wloss, loss_sum, count, probe])
        if self.shard_update:
            state = self._zero1_update(
                state, grads, jax.random.fold_in(rng, 0x7FFF), with_comm
            )
            if with_comm:
                with jax.named_scope(scopes.COMBINE):
                    metrics = jax.lax.psum(metrics, self._axis_arg)
            return state, metrics
        new_residual = state.comm_residual
        if with_comm:
            if self.hier:
                grads, new_residual = self._hier_combine(
                    grads, jax.random.fold_in(rng, 0x7FFF), state.comm_residual
                )
            elif self.compress_grads == "int8":
                grads = self._compressed_psum(grads, rng)
            else:
                with jax.named_scope(scopes.COMBINE):
                    grads = jax.lax.psum(grads, self._axis_arg)
            with jax.named_scope(scopes.COMBINE):
                metrics = jax.lax.psum(metrics, self._axis_arg)
        return self._apply_update(state, grads, comm_residual=new_residual), metrics

    def _compressed_psum(self, grads, rng):
        """Quantized FLAT gradient collective (compressed-allreduce family):
        per leaf, one stochastic-rounded int8 all-reduce hop over the whole
        mesh (parallel/wire.py — E[dequant] == grad, so no error-feedback
        buffer is required), summed in int16 on the wire — half the bytes of
        an f32 collective. The per-leaf scale pmax is a scalar, negligible
        next to the tensor traffic. The hierarchical combine generalizes
        this into the cross-host hop of _hier_combine."""
        leaves, treedef = jax.tree_util.tree_flatten(grads)
        n = len(self.mesh.devices.flat)
        out = []
        with jax.named_scope(scopes.COMBINE):
            for i, g in enumerate(leaves):
                key = jax.random.fold_in(rng, i + 0x7FFF)
                total, _sent = wirefmt.compressed_reduce(
                    g, key, self._axis_arg, n, "int8"
                )
                out.append(total.astype(g.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    def _zero1_update(
        self, state, local_grads, rng, with_comm: bool, local_index=None
    ):
        """Generic sharded optimizer update (ZeRO-1 analogue, arXiv
        2004.13336) over an ARBITRARY optax transform: ravel the gradient
        tree ONCE, reduce-scatter into this device's 1/n chunk, run
        ``tx.update`` on the chunk against the chunked opt state and the
        matching flat param chunk (param-dependent transforms — adamw's
        weight decay — see exactly their slice), all-gather the update
        delta, apply. Exact for elementwise transforms — identical per
        element to the replicated per-leaf update (the update shard is
        uniform even when data shards are not, which is why this composes
        with DBS).

        Wire composition (PR-12, N-level since ISSUE 17): on a tree mesh
        the reduce-scatter walks the tree — full-precision over the
        innermost (fastest) axis, then one EF'd hop per outer level on
        that hop's ``grad_comm_wires`` codec, the outermost hop a
        compressed all-reduce of the top chunk; each device then keeps
        its mixed-radix flat block (innermost axis most significant —
        parallel/mesh.py zero1_chunk_axes), so the two-level layout
        ``d*H + h`` is unchanged. On the flat mesh,
        ``compress_grads='int8'`` rides the quantized reduce-scatter
        (parallel/wire.py compressed_reduce_scatter). ``with_comm=False``
        builds the comm-free probe twin: same FLOPs shape, collectives
        replaced by local slices/pads (output is discarded) — and, with
        ``local_index`` given, the AXIS-FREE twin the scan-mode superstep
        runs under plain jit (no shard_map axis context): the caller
        supplies the flat chunk index instead of ``_data_axis_index()``.
        On the 1-device mesh that path exists on, chunk == padded and the
        slice/pad pair is the identity the size-1 collectives would be."""
        import jax.flatten_util

        # one `update` scope over the whole sharded step; its collectives sit
        # in a nested `combine`, which obs/scopes.py reads as the innermost
        with jax.named_scope(scopes.UPDATE):
            opt = state.opt_state
            n = len(self.mesh.devices.flat)
            flat_g, unravel = jax.flatten_util.ravel_pytree(local_grads)
            t_real = flat_g.size
            # the ctor-validated padding is THE convention (train/state.py
            # zero1_padded_size) — recomputing it here could silently diverge
            # from the state conversion's chunk layout
            padded = self.zero1_padded
            assert padded % n == 0 and padded >= t_real, (padded, n, t_real)
            flat_g = jnp.pad(flat_g, (0, padded - t_real))
            chunk = padded // n
            new_residual = state.comm_residual
            key = jax.random.fold_in(rng, 0x2E01)
            if self.hier:
                names = self.axes
                sizes = tuple(int(self.mesh.shape[a]) for a in names)
                k = len(names) - 1
                idxs = [jax.lax.axis_index(a) for a in names]
                # same padding convention as attach_comm_residual(pad_multiple=n)
                widths = wirefmt.tree_hop_widths(t_real, sizes, pad_multiple=n)
                assert widths[-1] == padded, (widths, padded)
                # this device's flat block: mixed-radix offset with the
                # innermost axis most significant (zero1_chunk_axes order) —
                # exactly where the scatter cascade below lands its chunk
                off = idxs[0] * chunk
                for i in range(1, k + 1):
                    off = off + idxs[i] * widths[i - 1]
                if with_comm:
                    # innermost reduce-scatter at full precision (ICI): the
                    # device's index along the fastest axis picks its
                    # widths[k-1] slice of the in-group sum
                    with jax.named_scope(scopes.COMBINE):
                        v = jax.lax.psum_scatter(
                            flat_g, names[k], scatter_dimension=0, tiled=True
                        )
                    res = state.comm_residual
                    new_rows = list(res) if res is not None else [None] * k
                    # middle hops k-1..1: EF'd compressed reduce-scatter on
                    # each hop's wire, vector shrinking by sizes[i] per hop
                    for i in range(k - 1, 0, -1):
                        vi = v + (res[i][0] if res is not None else 0.0)
                        with jax.named_scope(scopes.COMBINE):
                            v, sent = wirefmt.compressed_reduce_scatter_ef(
                                vi,
                                jax.random.fold_in(key, i),
                                names[i],
                                sizes[i],
                                self.grad_comm_wires[i],
                            )
                        new_rows[i] = (vi - sent)[None]
                    # top hop: compressed all-reduce of the widths[0] chunk
                    v0 = v + (res[0][0] if res is not None else 0.0)
                    with jax.named_scope(scopes.COMBINE):
                        total, sent = wirefmt.compressed_reduce(
                            v0,
                            jax.random.fold_in(key, 0),
                            names[0],
                            sizes[0],
                            self.grad_comm_wires[0],
                        )
                    new_rows[0] = (v0 - sent)[None]
                    new_residual = tuple(new_rows)
                    # re-split across the top level: index a_0 owns the a_0-th
                    # 1/s_0 sub-slice of the fully reduced top chunk
                    g_chunk = jax.lax.dynamic_slice(
                        total, (idxs[0] * chunk,), (chunk,)
                    )
                else:
                    g_chunk = jax.lax.dynamic_slice(flat_g, (off,), (chunk,))
            else:
                # A size-1 data axis makes the uncompressed collectives
                # identities — route the slice twin instead, so single-device
                # topologies compile the SAME flat-update program on every
                # dispatch path (per-step combine twin, fused shard body,
                # scan-mode superstep). The scan x zero1 bitwise-parity
                # contract rides on the lowering being shared, not merely
                # value-equal: XLA contracts the update chain differently
                # around a collective than around a slice (ulp-scale drift no
                # optimization_barrier placement removes). The quantized wire
                # stays collective — stochastic rounding is no identity even
                # over one device.
                if n == 1 and self.compress_grads != "int8":
                    with_comm = False
                    if local_index is None:
                        local_index = 0
                off = (
                    self._data_axis_index() if local_index is None else local_index
                ) * chunk
                if with_comm:
                    with jax.named_scope(scopes.COMBINE):
                        if self.compress_grads == "int8":
                            g_chunk = wirefmt.compressed_reduce_scatter(
                                flat_g, key, self._axis_arg, n, "int8"
                            )
                        else:
                            g_chunk = jax.lax.psum_scatter(
                                flat_g, self._axis_arg, scatter_dimension=0, tiled=True
                            )
                else:
                    g_chunk = jax.lax.dynamic_slice(flat_g, (off,), (chunk,))
            flat_p, _ = jax.flatten_util.ravel_pytree(state.params)
            flat_p = jnp.pad(flat_p.astype(jnp.float32), (0, padded - t_real))
            p_chunk = jax.lax.dynamic_slice(flat_p, (off,), (chunk,))
            updates_chunk, opt_state = self.tx.update(g_chunk, opt, p_chunk)
            if with_comm:
                with jax.named_scope(scopes.COMBINE):
                    if self.hier:
                        # gather back in layout order, outermost axis first (each
                        # gather rebuilds the next-wider hop vector, inverting the
                        # scatter cascade LIFO), innermost last (rebuilds the flat
                        # vector)
                        delta = updates_chunk
                        for a in self.axes:
                            delta = jax.lax.all_gather(delta, a, tiled=True)
                    else:
                        delta = jax.lax.all_gather(
                            updates_chunk, self._axis_arg, tiled=True
                        )
            else:
                delta = jax.lax.dynamic_update_slice(
                    jnp.zeros((padded,), updates_chunk.dtype), updates_chunk, (off,)
                )
            params = jax.tree_util.tree_map(
                lambda p, u: p + u.reshape(p.shape).astype(p.dtype),
                state.params,
                unravel(delta[:t_real]),
            )
            return state.replace(
                params=params, opt_state=opt_state, step=state.step + 1,
                comm_residual=new_residual,
            )

    @functools.cached_property
    def fused_step(self):
        """One-jit SPMD step for uniform plans with one worker per device.
        Inputs: state (replicated), batch [D*b, ...] (sharded on 'data'),
        per-example weights, per-device slow_iters [D], scalar seed."""

        def per_shard(state, x, y, w, slow_iters, seed):
            return self._fused_shard_body(state, x, y, w, slow_iters[0], seed)

        bx = self._batch_entry
        sharded = shard_map(
            _named("fused_step", per_shard),
            mesh=self.mesh,
            in_specs=(self._state_spec(), P(bx), P(bx), P(bx), P(bx), P()),
            out_specs=(self._state_spec(), P()),
            check_vma=False,
        )
        return jax.jit(sharded, donate_argnums=self._state_donate)

    @functools.cached_property
    def fused_epoch(self):
        """A whole epoch in ONE dispatch: lax.scan over the step axis inside
        the SPMD program. Inputs are the full epoch's batches
        [steps, D*b, ...] (sharded on the batch axis); state is carried by the
        scan. The dbs-off / converged-uniform fast path — no per-step Python,
        full XLA pipelining."""

        def per_shard(state, xs, ys, ws_, slow_iters, seed):
            def body(state, inp):
                x, y, w = inp
                return self._fused_shard_body(state, x, y, w, slow_iters[0], seed)

            state, metrics = jax.lax.scan(body, state, (xs, ys, ws_))
            return state, jnp.sum(metrics, axis=0)

        bx = self._batch_entry
        sharded = shard_map(
            _named("fused_epoch", per_shard),
            mesh=self.mesh,
            in_specs=(
                self._state_spec(),
                P(None, bx),
                P(None, bx),
                P(None, bx),
                P(bx),
                P(),
            ),
            out_specs=(self._state_spec(), P()),
            check_vma=False,
        )
        return jax.jit(sharded, donate_argnums=self._state_donate)

    @functools.cached_property
    def fused_epoch_idx(self):
        """``fused_epoch`` fed by the device-resident data cache: the train
        arrays are passed replicated (already on device — no re-transfer) and
        each scanned step gathers its rows by index on device. The per-epoch
        host->device traffic is [steps, D*b] int32 + f32 weights instead of
        the batches themselves — the whole-dataset epoch transfer disappears."""

        def per_shard(state, train_x, train_y, idxs, ws_, slow_iters, seed):
            def body(state, inp):
                idx_s, w = inp
                x = jnp.take(train_x, idx_s, axis=0, mode="clip")
                y = jnp.take(train_y, idx_s, axis=0, mode="clip")
                return self._fused_shard_body(state, x, y, w, slow_iters[0], seed)

            state, metrics = jax.lax.scan(body, state, (idxs, ws_))
            return state, jnp.sum(metrics, axis=0)

        bx = self._batch_entry
        sharded = shard_map(
            _named("fused_epoch_idx", per_shard),
            mesh=self.mesh,
            in_specs=(
                self._state_spec(),
                P(),
                P(),
                P(None, bx),
                P(None, bx),
                P(bx),
                P(),
            ),
            out_specs=(self._state_spec(), P()),
            check_vma=False,
        )
        return jax.jit(sharded, donate_argnums=self._state_donate)

    def _fused_probe(self, name: str, with_comm: bool):
        """Non-donating single-step twin of ``fused_step`` for timing probes.
        ``with_comm=False`` drops the psums (see _fused_shard_body); outputs
        are discarded by the caller, so the unreplicated no-comm outputs are
        harmless (check_vma is off)."""

        def per_shard(state, x, y, w, slow_iters, seed):
            return self._fused_shard_body(
                state, x, y, w, slow_iters[0], seed, with_comm=with_comm
            )

        bx = self._batch_entry
        sharded = shard_map(
            _named(name, per_shard),
            mesh=self.mesh,
            in_specs=(self._state_spec(), P(bx), P(bx), P(bx), P(bx), P()),
            out_specs=(self._state_spec(), P()),
            check_vma=False,
        )
        return jax.jit(sharded)

    @functools.cached_property
    def fused_step_probe(self):
        return self._fused_probe("fused_step_probe", with_comm=True)

    @functools.cached_property
    def fused_step_nocomm(self):
        return self._fused_probe("fused_step_nocomm", with_comm=False)

    @functools.cached_property
    def comm_probe(self):
        """Standalone gradient collective: psum of a grads-shaped tree over
        the mesh. Fallback sync-time meter when the full-vs-nocomm delta is
        below timer noise — the closest analogue of the reference's blocking
        allreduce wait (dbs.py:296-298)."""

        axes = self._axis_arg

        def per_shard(tree):
            with jax.named_scope(scopes.COMBINE):
                return jax.lax.psum(tree, axes)

        sharded = shard_map(
            _named("comm_probe", per_shard),
            mesh=self.mesh,
            in_specs=(P(),),
            out_specs=P(),
            check_vma=False,
        )
        return jax.jit(sharded)

    @functools.cached_property
    def fused_eval_step(self):
        """Sharded evaluation over the mesh — the whole test batch split across
        devices. (The reference redundantly evaluates the FULL test set on
        every rank, dbs.py:147; sharding it is the same math, ws× faster.)"""
        spec = self.spec
        apply_fn = spec.module.apply
        prep = self._prep_images
        axes = self._axis_arg

        def per_shard(params, x, y, mask):
            with jax.named_scope(scopes.EVAL):
                xf = prep(x, jax.random.PRNGKey(0), train=False)
                out = apply_fn(params, xf, train=False)
                losses = _per_example_loss(spec, out, y)
                m = mask.astype(jnp.float32)
                pred = jnp.argmax(out, axis=-1)
                stats = jnp.stack(
                    [jnp.sum(losses * m), jnp.sum((pred == y).astype(jnp.float32) * m), jnp.sum(m)]
                )
                return jax.lax.psum(stats, axes)

        bx = self._batch_entry
        sharded = shard_map(
            _named("fused_eval_step", per_shard),
            mesh=self.mesh,
            in_specs=(P(), P(bx), P(bx), P(bx)),
            out_specs=P(),
            check_vma=False,
        )
        return jax.jit(sharded)


def stack_partials(partials_by_device, mesh: Mesh):
    """Zero-copy assembly of per-device gradient partials (each with a leading
    [1, ...] axis, living on its device) into global arrays sharded over the
    mesh — the input of combine_update. This is the moment the reference would
    enter its gloo allreduce (dbs.py:296); here it is just array surgery, the
    actual reduction happens inside the combine_update collective.

    Multi-host: each process passes only its local devices' partials (the
    mesh's addressable slice); JAX matches shards to mesh positions by device,
    and the cross-host reduction happens inside the combine collective over
    DCN."""
    n_local = len(partials_by_device)
    n_global = len(mesh.devices.flat)
    assert n_local == len([d for d in mesh.devices.flat if d.process_index == jax.process_index()])
    from dynamic_load_balance_distributeddnn_tpu.parallel.mesh import (
        mesh_batch_axes,
    )

    sharding = NamedSharding(mesh, P(mesh_batch_axes(mesh)))

    leaves_by_dev = [jax.tree_util.tree_leaves(p) for p in partials_by_device]
    treedef = jax.tree_util.tree_structure(partials_by_device[0])
    stacked_leaves = []
    for li in range(len(leaves_by_dev[0])):
        shards = [leaves_by_dev[d][li] for d in range(n_local)]
        shape = (n_global,) + tuple(shards[0].shape[1:])
        stacked_leaves.append(
            jax.make_array_from_single_device_arrays(shape, sharding, shards)
        )
    return jax.tree_util.tree_unflatten(treedef, stacked_leaves)


def shard_views(tree, devices):
    """Per-device single-device views of a replicated global tree: one tree
    per requested device whose leaves are that device's local shards (no
    copies)."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    views = [[] for _ in devices]
    index = {dev: i for i, dev in enumerate(devices)}
    for leaf in leaves:
        hit = 0
        for s in leaf.addressable_shards:
            i = index.get(s.device)
            if i is not None:
                views[i].append(s.data)
                hit += 1
        assert hit == len(devices), "replicated tree missing shards for mesh devices"
    return [jax.tree_util.tree_unflatten(treedef, v) for v in views]

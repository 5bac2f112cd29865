"""The ``SparseOperator`` protocol: one mesh-aware, differentiable
linear-operator API over single-device and distributed spMVM.

The paper's promise is that callers see ``y = A x`` while storage
format, permutation and halo plumbing stay hidden.  This module is that
promise as an API: every operator — whatever lives inside — offers

* ``shape`` / ``dtype`` and ``__matmul__`` sugar (``op @ x`` dispatches
  1-D -> ``matvec``, 2-D -> ``matmat``), both in the ORIGINAL basis;
* a transpose family: ``op.T`` is a lazy view whose ``matvec`` is
  ``op.rmatvec``.  Blocked formats run ``A^T x`` as a scatter-accumulate
  over their stored column indices (``kernels.ref.blocked_rmatvec_ref``),
  or — with ``transpose="device"`` — through a CSC-of-blocks device
  build (``formats.csr_transpose`` fed back through the forward
  kernels); CSR swaps its gather and its segment ids;
* custom derivative rules so ``jax.grad`` (and ``jax.jvp``) works
  through both the stored values and x, even when the forward pass ran
  the Pallas kernels (tangents and cotangents ride the jnp ref path —
  same math, and ``d(Ax)/d(val)`` reuses the forward gather structure);
* pytree registration, so operators flow through ``jit`` / ``shard_map``
  / ``lax.while_loop`` carriers and can sit inside model param trees.

Two implementations cover the repo's stacks:

* :class:`DeviceOperator` — wraps the dispatch layer's
  ``kernels.ops.SparseDevice`` (CSR / ELLPACK-R / pJDS / SELL-C-sigma /
  windowed SELL-C-sigma / CMRS, chosen by ``format="auto"``).  Build
  with :func:`operator`.
* :class:`DistOperator` — wraps ``core.dist_spmv`` (row-partitioned
  SELL-windowed storage + gathered halo exchange over a mesh axis).
  Build with :func:`dist_operator`.  Its transpose is the transposed
  partition — ``A^T``'s halo is the mirror coupling, measured the same
  way — so ``op.T`` and x-gradients stay fully distributed.

A mesh operator and a local operator are interchangeable anywhere a
``SparseOperator`` (or bare matvec callable) is accepted — in
particular every solver in ``core.solvers`` runs unmodified on both.
See DESIGN.md §8.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import formats as F
from repro.core import dist_spmv as D
from repro.core import perf_model as PM
from repro.kernels import ops
from repro.kernels._backend import OUT_BLOCKS

__all__ = [
    "SparseOperator",
    "DeviceOperator",
    "TransposeOperator",
    "DistOperator",
    "operator",
    "dist_operator",
]


# --------------------------------------------------------------------------
# The protocol
# --------------------------------------------------------------------------
class SparseOperator:
    """Abstract linear operator y = A x in the original basis.

    Implementations provide ``shape``, ``dtype``, ``matvec``, ``matmat``,
    ``rmatvec``, ``rmatmat`` and (square operators) ``diagonal``; the
    base class supplies the ``@`` sugar and the lazy transpose view.
    Implementations must also be registered pytrees.
    """

    shape: tuple

    @property
    def dtype(self):
        raise NotImplementedError

    def matvec(self, x: jax.Array) -> jax.Array:
        """y = A x: x (shape[1],) [or longer, padded] -> y (shape[0],)."""
        raise NotImplementedError

    def matmat(self, x: jax.Array) -> jax.Array:
        """Y = A X: X (shape[1], k) -> Y (shape[0], k)."""
        raise NotImplementedError

    def rmatvec(self, y: jax.Array) -> jax.Array:
        """x = A^T y: y (shape[0],) -> x (shape[1],)."""
        raise NotImplementedError

    def rmatmat(self, y: jax.Array) -> jax.Array:
        """X = A^T Y: Y (shape[0], k) -> X (shape[1], k)."""
        raise NotImplementedError

    def diagonal(self) -> jax.Array:
        """diag(A) for square operators (the Jacobi preconditioner)."""
        raise NotImplementedError

    @property
    def T(self) -> "SparseOperator":
        """Lazy transpose view, memoized so ``op.T is op.T`` (repeated
        solves on the view reuse one solver closure / jit entry) and
        ``op.T.T is op``."""
        t = getattr(self, "_T", None)
        if t is None:
            t = TransposeOperator(self)
            self._T = t
        return t

    def __matmul__(self, x):
        x = jnp.asarray(x)
        if x.ndim == 1:
            return self.matvec(x)
        if x.ndim == 2:
            return self.matmat(x)
        raise ValueError(f"operator @ x expects 1-D or 2-D x; got {x.shape}")


@jax.tree_util.register_pytree_node_class
class TransposeOperator(SparseOperator):
    """Lazy ``A^T`` view: forwards to the base operator's r-methods."""

    def __init__(self, base: SparseOperator):
        self.base = base

    @property
    def shape(self):
        s = self.base.shape
        return (s[1], s[0])

    @property
    def dtype(self):
        return self.base.dtype

    def matvec(self, x):
        return self.base.rmatvec(x)

    def matmat(self, x):
        return self.base.rmatmat(x)

    def rmatvec(self, y):
        return self.base.matvec(y)

    def rmatmat(self, y):
        return self.base.matmat(y)

    def diagonal(self):
        return self.base.diagonal()      # diag(A^T) == diag(A)

    @property
    def T(self):
        return self.base

    def tree_flatten(self):
        return (self.base,), None

    @classmethod
    def tree_unflatten(cls, _aux, children):
        return cls(children[0])


# --------------------------------------------------------------------------
# Differentiable application (single device)
# --------------------------------------------------------------------------
def _ref_apply(dev: ops.SparseDevice, x: jax.Array) -> jax.Array:
    """The pure-jnp (gather + segment-sum) application — differentiable
    by construction; the custom derivative rule below differentiates
    THIS, so grads are exact for the kernel backend too (same math)."""
    return dev.matvec(x, backend="ref")


@functools.partial(jax.custom_jvp, nondiff_argnums=(2,))
def _device_apply(dev: ops.SparseDevice, x: jax.Array, backend: str):
    return dev.matvec(x, backend=backend)


@_device_apply.defjvp
def _device_apply_jvp(backend, primals, tangents):
    dev, x = primals
    # The tangent rides the ref path: A(val_dot) x + A x_dot, built from
    # transposable jnp ops — so REVERSE mode falls out by transposition
    # (d(Ax)/dx^T g = A^T g, the scatter-accumulate transpose, and
    # d(Ax)/d(val)^T g reuses the forward gather; integer leaves carry
    # float0) while FORWARD mode (jax.jvp) works directly.  The primal
    # still runs the requested backend (Pallas kernels have no rules).
    y = _device_apply(dev, x, backend)
    y_dot = jax.jvp(_ref_apply, primals, tangents)[1]
    return y, y_dot


@jax.tree_util.register_pytree_node_class
class DeviceOperator(SparseOperator):
    """Single-device :class:`SparseOperator` over a dispatch-layer
    ``SparseDevice`` (format chosen once, conversion cached).

    ``t_dev``, when present, is the CSC-of-blocks device build of
    ``A^T`` (``operator(a, transpose="device")``): ``rmatvec`` then runs
    the FORWARD kernels on the transposed operand instead of the
    scatter-accumulate ref.  ``backend="auto"`` resolves per call in
    ``kernels.ops.resolve_backend``.
    """

    def __init__(self, dev: ops.SparseDevice,
                 t_dev: Optional[ops.SparseDevice] = None,
                 backend: ops.Backend = "auto"):
        self.dev = dev
        self.t_dev = t_dev
        self.backend = backend
        self._diag = None                 # lazy; not part of the pytree

    # -- structure ---------------------------------------------------------
    @property
    def shape(self):
        return self.dev.shape

    @property
    def fmt(self) -> str:
        return self.dev.fmt

    @property
    def dtype(self):
        return self.values.dtype

    @property
    def stored_slots(self) -> int:
        """Value slots one forward apply streams, padding included."""
        return self.dev.dev.stored_slots

    @property
    def window_share(self) -> float:
        """Share of the non-zeros served from a window of x inside the
        kernel (``ops._Operand.window_share``)."""
        return self.dev.dev.window_share

    @property
    def fused_unpermute(self) -> float:
        """Share of the rows whose unpermute runs inside the kernel
        (``ops._Operand.fused_unpermute``)."""
        return self.dev.dev.fused_unpermute

    @property
    def grid_fill(self) -> float:
        """Share of the kernel grid's steps that compute a chunk
        (``ops._Operand.grid_fill``)."""
        return self.dev.dev.grid_fill

    @property
    def values(self) -> jax.Array:
        """The stored value leaf (the differentiable parameters)."""
        return self.dev.dev.val

    def with_values(self, val: jax.Array) -> "DeviceOperator":
        """Same sparsity structure, new stored values — the handle
        ``jax.grad`` differentiates through:
        ``jax.grad(lambda v: loss(op.with_values(v) @ x))(op.values)``.
        Drops any ``t_dev`` (its values live in transposed order)."""
        inner = dataclasses.replace(self.dev.dev, val=val)
        return DeviceOperator(dataclasses.replace(self.dev, dev=inner),
                              backend=self.backend)

    # -- application -------------------------------------------------------
    def matvec(self, x, backend: Optional[ops.Backend] = None):
        return _device_apply(self.dev, x, backend or self.backend)

    def matmat(self, x, backend: Optional[ops.Backend] = None):
        return _device_apply(self.dev, x, backend or self.backend)

    def rmatvec(self, y, backend: Optional[ops.Backend] = None):
        if self.t_dev is not None:
            return _device_apply(self.t_dev, y, backend or self.backend)
        return self.dev.rmatvec(y)

    def rmatmat(self, y, backend: Optional[ops.Backend] = None):
        if self.t_dev is not None:
            return _device_apply(self.t_dev, y, backend or self.backend)
        return self.dev.rmatmat(y)

    def diagonal(self):
        if self.shape[0] != self.shape[1]:
            raise ValueError("diagonal requires a square operator")
        if self._diag is None:
            d = _device_diagonal(self.dev)
            if isinstance(d, jax.core.Tracer):
                return d         # never cache a tracer past its trace
            self._diag = d
        return self._diag

    # -- pytree ------------------------------------------------------------
    def tree_flatten(self):
        return (self.dev, self.t_dev), (self.backend,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], t_dev=children[1], backend=aux[0])


def _device_diagonal(sd: ops.SparseDevice) -> jax.Array:
    """diag(A) straight from the device layout (no host matrix needed;
    the operand's ``diagonal``).  A preprocessing permutation
    (``reorder=``) stores B = P A P^T, whose diagonal is diag(A)
    permuted — ``diag(A) = diag(B)[pre_inv]``."""
    dg = sd.dev.diagonal(sd.shape[0])
    if sd.pre_inv is not None:
        dg = dg[sd.pre_inv]
    return dg


# --------------------------------------------------------------------------
# Distributed operator
# --------------------------------------------------------------------------
def _linear_with_transpose(fwd, bwd, x):
    """Wrap a linear sharded application with an explicit transpose rule:
    gradients w.r.t. x flow through ``bwd`` (the transposed partition's
    forward pass) instead of JAX trying to transpose the halo exchange."""
    @jax.custom_vjp
    def apply(xx):
        return fwd(xx)

    apply.defvjp(lambda xx: (fwd(xx), None), lambda _res, g: (bwd(g),))
    return apply(x)


@jax.tree_util.register_pytree_node_class
class DistOperator(SparseOperator):
    """Mesh-distributed :class:`SparseOperator` over a ``DistPJDS``
    row partition (``core.dist_spmv``).

    Vectors are GLOBAL padded vectors of length ``n_global_pad``,
    sharded along ``axis`` (``P(axis)`` / ``P(axis, None)`` for blocks);
    the operator returns the same sharding.  ``t_dist``, when present,
    is the row partition of ``A^T`` — the transpose halo is the mirror
    coupling, measured at partition time like the forward one — and
    powers ``rmatvec``/``op.T`` plus the x-cotangent of ``jax.grad``.
    Gradients w.r.t. the distributed stored values are not defined
    (inference/solver operator; train on :class:`DeviceOperator`).
    """

    def __init__(self, dist: D.DistPJDS, mesh,
                 t_dist: Optional[D.DistPJDS] = None,
                 diag: Optional[jax.Array] = None,
                 axis: str = "data", mode: D.Mode = "overlap",
                 backend: ops.Backend = "auto", halo: D.Halo = "gathered",
                 pre_perm: Optional[jax.Array] = None,
                 pre_inv: Optional[jax.Array] = None):
        self.dist = dist
        self.mesh = mesh
        self.t_dist = t_dist
        self.diag = diag
        self.axis = axis
        self.mode = mode
        self.backend = backend
        self.halo = halo
        # Preprocessing (reorder=) permutation over the PADDED global
        # index space (identity on the pad tail): the partition holds
        # B = P A P^T and every apply sandwiches, so callers stay in
        # the original basis.  ``diag`` is already original-basis.
        self.pre_perm = pre_perm
        self.pre_inv = pre_inv
        self._fwd_cache = {}     # (which partition, multi_rhs) -> closure

    # -- structure ---------------------------------------------------------
    @property
    def shape(self):
        n = self.dist.n_global_pad
        return (n, n)

    @property
    def n_rows(self) -> int:
        """Unpadded global row count (rows past this are zero)."""
        return self.dist.n_rows

    @property
    def dtype(self):
        return self.dist.loc_val.dtype

    @property
    def stored_slots(self) -> int:
        """Value slots one forward apply streams, padding included,
        summed over the devices: the local and the remote operand."""
        return int(self.dist.loc_val.size + self.dist.rem_val.size)

    @property
    def window_share(self) -> float:
        """The partition gathers every slot in XLA: no windows."""
        return 0.0

    @property
    def fused_unpermute(self) -> float:
        """The partition unsorts y in XLA, outside its kernels."""
        return 0.0

    @property
    def grid_fill(self) -> float:
        """Share of the local and remote kernels' grid steps that compute
        a chunk, per device.  Every device's operands are padded to one
        extent, and the padding chunks are computed, so each device reads
        the same share and this is also their mean."""
        d = self.dist
        n_groups = max(-(-d.n_blocks // OUT_BLOCKS), 1)
        n_loc, n_rem = d.loc_chunk_map.shape[1], d.rem_chunk_map.shape[1]
        steps = n_groups * ((d.loc_max_chunks or n_loc)
                            + (d.rem_max_chunks or n_rem))
        return (n_loc + n_rem) / max(steps, 1)

    # -- application -------------------------------------------------------
    def _fwd(self, dist, multi_rhs):
        # Memoized per instance: the shard_map closure is built once per
        # (partition, arity) — rebuilding per call would discard the
        # build-once amortization AND defeat the solvers' jit cache.
        key = (dist is self.t_dist, multi_rhs)
        fn = self._fwd_cache.get(key)
        if fn is None:
            fn = D._make_dist_op(dist, self.mesh, self.axis, self.mode,
                                 self.backend, self.halo,
                                 multi_rhs=multi_rhs)
            self._fwd_cache[key] = fn
        return fn

    def _sandwich(self, apply, v):
        return ops.sandwich(self.pre_perm, self.pre_inv, apply, v)

    def matvec(self, x):
        fwd = self._fwd(self.dist, multi_rhs=False)
        if self.t_dist is None:
            return self._sandwich(fwd, x)
        return self._sandwich(lambda v: _linear_with_transpose(
            fwd, self._fwd(self.t_dist, multi_rhs=False), v), x)

    def matmat(self, x):
        fwd = self._fwd(self.dist, multi_rhs=True)
        if self.t_dist is None:
            return self._sandwich(fwd, x)
        return self._sandwich(lambda v: _linear_with_transpose(
            fwd, self._fwd(self.t_dist, multi_rhs=True), v), x)

    def rmatvec(self, y):
        if self.t_dist is None:
            raise ValueError(
                "this DistOperator was built without a transpose partition; "
                "use dist_operator(m, mesh, transpose='device')")
        return self._sandwich(lambda v: _linear_with_transpose(
            self._fwd(self.t_dist, multi_rhs=False),
            self._fwd(self.dist, multi_rhs=False), v), y)

    def rmatmat(self, y):
        if self.t_dist is None:
            raise ValueError(
                "this DistOperator was built without a transpose partition; "
                "use dist_operator(m, mesh, transpose='device')")
        return self._sandwich(lambda v: _linear_with_transpose(
            self._fwd(self.t_dist, multi_rhs=True),
            self._fwd(self.dist, multi_rhs=True), v), y)

    def diagonal(self):
        if self.diag is None:
            raise ValueError("this DistOperator carries no diagonal; "
                             "build it with dist_operator(m, mesh)")
        return self.diag

    # -- pytree ------------------------------------------------------------
    def tree_flatten(self):
        return ((self.dist, self.t_dist, self.diag, self.pre_perm,
                 self.pre_inv),
                (self.mesh, self.axis, self.mode, self.backend, self.halo))

    @classmethod
    def tree_unflatten(cls, aux, children):
        dist, t_dist, diag, pre_perm, pre_inv = children
        mesh, axis, mode, backend, halo = aux
        return cls(dist, mesh, t_dist=t_dist, diag=diag, axis=axis,
                   mode=mode, backend=backend, halo=halo,
                   pre_perm=pre_perm, pre_inv=pre_inv)


# --------------------------------------------------------------------------
# Factories
# --------------------------------------------------------------------------
def _built(op):
    """``op``, with its stored slots, its window share, the share of its
    rows unpermuted inside the kernel and its grid fill noted
    (``obs.gauge``) as those of the operator built last."""
    obs.gauge("repro.stored_slots", op.stored_slots)
    obs.gauge("repro.window_share", op.window_share)
    obs.gauge("repro.fused_unpermute", op.fused_unpermute)
    obs.gauge("repro.grid_fill", op.grid_fill)
    return op


@obs.span("repro.operator.build")
def operator(
    a: Union[F.CSRMatrix, np.ndarray, ops.SparseDevice, SparseOperator],
    format: ops.FormatName = "auto",
    *,
    backend: ops.Backend = "auto",
    transpose: str = "ref",
    **convert_kwargs,
) -> SparseOperator:
    """Wrap ``a`` as a single-device :class:`SparseOperator`.

    ``a`` may be a host CSRMatrix, a dense ndarray, an existing
    ``SparseDevice``, or already an operator (returned unchanged).
    Conversion and caching ride :func:`kernels.ops.as_device`;
    ``format``/``convert_kwargs`` (b_r, diag_align, sigma, chunk_l,
    dtype, index_dtype, tune, reorder) pass through — in
    particular ``reorder="auto"`` runs the priced RCM preprocessing
    stage (``core.reorder.preprocess``): the permutation is recorded on
    the device operand and every apply transparently permutes in and
    unpermutes out, so callers stay in the original basis (with
    ``transpose="device"`` each operand prices and sandwiches its own
    reorder independently), and
    ``dtype=jnp.bfloat16`` stores a compressed bf16 value stream (f32
    accumulation; ``op.dtype`` reports the storage dtype, results come
    back f32), ``index_dtype="auto"`` (the default) compresses the
    column indices to int16 whenever the column span fits, and
    ``tune="auto"`` replaces the static dispatch heuristic with the
    measured autotuner (``repro.tune``, DESIGN.md §9; with
    ``transpose="device"`` the transposed operand is tuned
    independently — its row statistics are A's COLUMN statistics).
    ``transpose="device"`` additionally converts
    ``A^T`` (``formats.csr_transpose`` — the CSC-of-blocks build) so
    ``op.T @ x`` runs the forward kernels; the default ``"ref"`` serves
    transposes from the scatter-accumulate refs with no extra storage.
    """
    if isinstance(a, SparseOperator):
        return a
    if isinstance(a, ops.SparseDevice):
        if format not in ("auto", a.fmt):
            raise ValueError(
                f"matrix already converted to {a.fmt!r}; asked for {format!r}")
        if transpose == "device":
            raise ValueError(
                "transpose='device' needs the host matrix to build the "
                "transposed operand; pass the CSRMatrix (or ndarray) "
                "instead of a SparseDevice")
        if transpose != "ref":
            raise ValueError(f"transpose must be 'ref' or 'device'; "
                             f"got {transpose!r}")
        return _built(DeviceOperator(a, backend=backend))
    if isinstance(a, np.ndarray):
        a = ops._dense_to_csr_cached(a)
    if not isinstance(a, F.CSRMatrix):
        raise TypeError(f"cannot build an operator from {type(a)}")
    dev = ops.as_device(a, format, **convert_kwargs)
    t_dev = None
    if transpose == "device":
        t_dev = ops.as_device(F.csr_transpose(a), format, **convert_kwargs)
    elif transpose != "ref":
        raise ValueError(f"transpose must be 'ref' or 'device'; "
                         f"got {transpose!r}")
    return _built(DeviceOperator(dev, t_dev=t_dev, backend=backend))


@obs.span("repro.operator.build")
def dist_operator(
    m: Union[F.CSRMatrix, D.DistPJDS],
    mesh,
    *,
    axis: str = "data",
    mode: str = "overlap",
    backend: ops.Backend = "auto",
    halo: str = "gathered",
    transpose: str = "device",
    b_r: int = 128,
    diag_align: int = 8,
    chunk_l: int = 8,
    halo_w: Optional[int] = None,
    sigma: Optional[int] = None,
    index_dtype="auto",
    tune: str = "off",
    grid=None,
    build_stages: bool = True,
    reorder: str = "off",
) -> DistOperator:
    """Partition ``m`` over ``mesh[axis]`` as a :class:`DistOperator`.

    With a host CSR, the transpose partition (``transpose="device"``,
    the default) and the global diagonal are built alongside, so
    ``op.T``, x-gradients and Jacobi preconditioning work distributed;
    ``transpose=None`` skips the second partition.  Passing an existing
    ``DistPJDS`` wraps it as-is (no transpose, no diagonal).
    ``index_dtype="auto"`` stores int16 column indices whenever the
    per-device slice spans fit (they are structurally bounded by the
    row partition — see ``dist_spmv.partition_csr``).

    ``grid=(gr, gc)`` partitions over a 2-D device grid (halo volume
    shrinks with ``gr``, the partial-sum reduction rides grid rows of
    ``gc`` — see ``dist_spmv``); the transpose partition uses the
    SWAPPED grid ``(gc, gr)``, since transposing exchanges the roles of
    the x halo and the y reduction.  ``grid="auto"`` picks the shape:
    measured by the tuner when ``tune`` is on, otherwise the
    model-cheapest of ``dist_spmv.grid_shapes`` under
    ``perf_model.predicted_dist_spmv_seconds``.

    ``halo="auto"`` resolves the gathered-vs-full exchange crossover
    from the installed ``perf_model`` calibration
    (``perf_model.choose_halo``; fit the per-message fixed costs with
    ``tune.calibrate.fit_link_calibration`` first, or let the tuner
    measure the winner directly).  ``mode="auto"`` likewise defers to
    the tuner, falling back to ``"overlap"``.

    ``reorder="auto"|"rcm"`` runs the priced RCM preprocessing stage
    (``core.reorder.preprocess``) on the host CSR before partitioning,
    with the halo term evaluated at this mesh's device count: "auto"
    applies the permutation only when the calibrated model predicts the
    reduced halo outweighs the per-apply permute sandwich, "rcm" forces
    it.  The operator records the permutation and every
    matvec/rmatvec/solve transparently permutes in and unpermutes out,
    so callers stay in the original row/column basis (the diagonal is
    stored original-basis too).

    ``tune="auto"|"force"`` measures the best tile height for the LOCAL
    and REMOTE operands independently (``repro.tune.tune_partition``;
    cached persistently like the single-device tuner) and partitions
    with the winners — the forward and transpose partitions are tuned
    separately, since ``A^T``'s halo coupling is the mirror structure.
    When any of ``grid``/``halo``/``mode`` is ``"auto"`` the tuner
    additionally sweeps the communication config over ``mesh`` (one
    timed sharded spMVM per candidate) and the measured winners fill
    the auto slots.
    """
    if isinstance(m, D.DistPJDS):
        if grid not in (None, "auto"):
            raise ValueError("grid cannot be changed on an existing "
                             "DistPJDS; partition the host CSR instead")
        if mode == "auto":
            mode = "overlap"
        if halo == "auto":
            halo = PM.choose_halo(m, mode=mode,
                                  value_bytes=m.loc_val.dtype.itemsize)
        return _built(DistOperator(D.place_on_mesh(m, mesh, axis), mesh,
                                   axis=axis, mode=mode, backend=backend,
                                   halo=halo))
    n_dev = mesh.shape[axis]
    if tune not in ("off", "auto", "force"):
        raise ValueError(f"tune must be 'off', 'auto' or 'force'; "
                         f"got {tune!r}")
    if reorder not in ("off", "auto", "rcm"):
        raise ValueError(f"reorder must be 'off', 'auto' or 'rcm'; "
                         f"got {reorder!r}")

    perm_host = inv_host = None
    diag_host = F.csr_diagonal(m)          # original basis, pre-reorder
    if reorder != "off":
        from repro.core import reorder as RO
        pp = RO.preprocess(m, reorder=reorder, n_dev=n_dev,
                           value_bytes=m.data.dtype.itemsize)
        if pp.applied:
            m = pp.matrix
            perm_host, inv_host = pp.perm, pp.inv_perm

    sweep = tune != "off" and ("auto" in (grid, halo, mode))

    def _chunks(mm, comm_sweep=False):
        if tune == "off":
            return chunk_l, None, None
        from repro import tune as T    # deferred: tune imports kernels.ops
        tp = T.tune_partition(mm, n_dev, b_r=b_r, diag_align=diag_align,
                              sigma=sigma, index_dtype=index_dtype,
                              force=(tune == "force"),
                              mesh=mesh if comm_sweep else None, axis=axis)
        return tp.chunk_l, tp.rem_chunk_l, tp

    cl, rcl, tp = _chunks(m, comm_sweep=sweep)
    if sweep:
        if grid == "auto":
            grid = tp.grid
        if halo == "auto" and tp.halo:
            halo = tp.halo
        if mode == "auto" and tp.mode:
            mode = tp.mode
    if mode == "auto":
        mode = "overlap"

    def _build(mm, g, clb, rclb, hw):
        return D.partition_csr(mm, n_dev, b_r=b_r, diag_align=diag_align,
                               chunk_l=clb, halo_w=hw, sigma=sigma,
                               index_dtype=index_dtype, rem_chunk_l=rclb,
                               grid=g, build_stages=build_stages)

    with obs.span("repro.convert"):
        if grid == "auto":
            # No measured sweep available: price every grid shape with
            # the (calibrated) perf model and keep the cheapest partition.
            cands = [_build(m, g if g != (n_dev, 1) else None, cl, rcl,
                            halo_w)
                     for g in D.grid_shapes(n_dev)]
            hs = ("gathered", "full") if halo == "auto" else (halo,)
            cost = [min(PM.predicted_dist_spmv_seconds(
                            d, halo=h, mode=mode,
                            value_bytes=d.loc_val.dtype.itemsize)
                        for h in hs) for d in cands]
            dist = cands[int(np.argmin(cost))]
        else:
            dist = _build(m, grid, cl, rcl, halo_w)
        if halo == "auto":
            halo = PM.choose_halo(dist, mode=mode,
                                  value_bytes=dist.loc_val.dtype.itemsize)

        t_dist = None
        if transpose == "device":
            mt = F.csr_transpose(m)
            cl_t, rcl_t, _ = _chunks(mt)
            g = dist.grid
            t_dist = _build(mt, (g[1], g[0]) if g else None, cl_t, rcl_t,
                            None)
        elif transpose is not None:
            raise ValueError(f"transpose must be 'device' or None; "
                             f"got {transpose!r}")
    dg = np.zeros(dist.n_global_pad, dtype=m.data.dtype)
    dg[: m.n_rows] = diag_host
    pre_perm = pre_inv = None
    if perm_host is not None:
        # Extend to the padded global space with an identity tail so the
        # sandwich gathers commute with the partition padding.
        tail = np.arange(m.n_rows, dist.n_global_pad)
        pre_perm = jnp.asarray(
            np.concatenate([perm_host, tail]).astype(np.int32))
        pre_inv = jnp.asarray(
            np.concatenate([inv_host, tail]).astype(np.int32))
    with obs.span("repro.transfer"):
        dist, t_dist, diag = obs.settled(D.place_on_mesh(
            (dist, t_dist, jnp.asarray(dg)), mesh, axis))
    return _built(DistOperator(dist, mesh, t_dist=t_dist, diag=diag,
                               axis=axis, mode=mode, backend=backend,
                               halo=halo, pre_perm=pre_perm,
                               pre_inv=pre_inv))

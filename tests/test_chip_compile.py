"""Compile every kernel of the main path for a described TPU v5e chip.

Interpret mode accepts tile shapes and in-kernel operations that the
TPU compiler (Mosaic) refuses, so the kernel-vs-ref parity tests cannot
show that the kernels run on the chip.  These tests lower and compile
each kernel through its dispatch entry point at sAMG's published
shapes (paper §1.3: 3.4M rows, ~7 non-zeros per row) under both dtype
policies, for one chip of a ``v5e:2x2`` topology that is described, not
attached.  Nothing runs; the compiler accepts or refuses.

The topology is described inside a module fixture (never at import),
and ``jax.default_backend`` is steered to ``"tpu"`` inside each test so
that ``backend="auto"`` and ``interpret=None`` resolve as they do on
the chip.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import formats as F
from repro.core import matrices as M
from repro.kernels import ops
from repro.kernels._backend import LANES, OUT_BLOCKS

# sAMG at its published size, stored the way ``ops.as_device`` builds
# it by default (b_r=128, chunk_l=16): every row block holds one
# 16-deep chunk at ~7 non-zeros per row; the ELLPACK-R slab is as deep
# as the longest generated row (30 plus the diagonal) rounded up.
_N = M._PUBLISHED["sAMG"]["dim"]
_B_R = 128
_CHUNK_L = 16
_N_BLOCKS = -(-_N // _B_R)
_N_PAD = _N_BLOCKS * _B_R
_TOTAL = _N_BLOCKS * _CHUNK_L
_ELL_DEPTH = 32
_GROUP_CHUNKS = 2 * OUT_BLOCKS
_N_RHS = 8
# The windowed SELL at the same size: the sAMG configuration's rows take
# windows of 3 units (24 rows of 128 lanes; ``formats.window_plan`` on
# its generator, 95.6% of the non-zeros in window) and leave ~4.4% of
# its ~22.6M non-zeros, 1M, to the XLA remainder.
_WINDOW = 3 * F.WINDOW_UNIT
_X_LEN = -(-_N // F.WINDOW_UNIT) * F.WINDOW_UNIT
_N_REM = 1_000_000
_REM_TAIL = -(-_N_REM // (_CHUNK_L * _B_R)) * _CHUNK_L
# its inverse permutation runs on to whole output groups of 8 row blocks
_N_OUT = -(-_N_BLOCKS // OUT_BLOCKS) * OUT_BLOCKS * _B_R

_POLICIES = [
    pytest.param(jnp.float32, jnp.int32, id="f32+int32"),
    pytest.param(jnp.bfloat16, jnp.int16, id="bf16+int16"),
]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:       # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def on_chip(monkeypatch, one_chip):
    """Resolve backend/interpret defaults as on the chip, with the
    persistent compilation cache off (a compile for a described chip is
    written to it but cannot be read back without one)."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield one_chip
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text, "no Pallas kernel in the program"
    return compiled


def _entry_root(text):
    """The ROOT instruction of a compiled program's entry computation."""
    body = text[text.index("\nENTRY "):]
    return next(line for line in body.splitlines()
                if line.lstrip().startswith("ROOT "))


def _kernel_operands(text, name):
    """The operands of the Pallas kernel ``name``'s custom call."""
    hit = re.search(rf"%{name}[\w.]* = \S+ custom-call\(([^)]*)\)", text)
    assert hit, f"no {name} kernel in the program"
    return re.sub(r"/\*[^*]*\*/", "", hit[1]).split(",")


def _blocked_shapes(vdt, idt, chunk_l=_CHUNK_L):
    return [((_TOTAL, _B_R), vdt), ((_TOTAL, _B_R), idt),
            ((_TOTAL // chunk_l,), jnp.int32)]


def _pjds(val, col, chunk_map, chunk_l=_CHUNK_L):
    return ops.PJDSDevice(val=val, col_idx=col, chunk_map=chunk_map,
                          row_block=chunk_map, n_blocks=_N_BLOCKS, b_r=_B_R,
                          chunk_l=chunk_l, max_chunks=_GROUP_CHUNKS)


def _sell(val, col, chunk_map, inv):
    return ops.SELLDevice(val=val, col_idx=col, chunk_map=chunk_map,
                          row_block=chunk_map, inv_perm=inv,
                          n_blocks=_N_BLOCKS, b_r=_B_R, chunk_l=_CHUNK_L,
                          sigma=8 * _B_R, max_chunks=_GROUP_CHUNKS)


@pytest.mark.parametrize("chunk_l", [8, _CHUNK_L])   # 8: half a bf16 tile
@pytest.mark.parametrize("vdt,idt", _POLICIES)
def test_pjds_spmv_compiles(on_chip, vdt, idt, chunk_l):
    def f(val, col, cm, x):
        return ops.pjds_matvec(_pjds(val, col, cm, chunk_l), x,
                               backend="auto")
    _compile(f, on_chip, *_blocked_shapes(vdt, idt, chunk_l),
             ((_N,), jnp.float32))


@pytest.mark.parametrize("vdt,idt", _POLICIES)
def test_sell_spmv_compiles(on_chip, vdt, idt):
    def f(val, col, cm, inv, x):
        return ops.sell_matvec(_sell(val, col, cm, inv), x, backend="auto")
    _compile(f, on_chip, *_blocked_shapes(vdt, idt),
             ((_N_PAD,), jnp.int32), ((_N,), jnp.float32))


@pytest.mark.parametrize("vdt,idt", _POLICIES)
def test_wsell_spmv_compiles(on_chip, vdt, idt):
    del idt
    def f(val, off, cm, wbase, inv, rem_row, rem_col, x):
        dev = ops.WSELLDevice(
            val=val, col_off=off, chunk_map=cm, row_block=cm, wbase=wbase,
            inv_perm=inv, rem_row=rem_row, rem_col=rem_col,
            n_blocks=_N_BLOCKS, b_r=_B_R, chunk_l=_CHUNK_L, sigma=8 * _B_R,
            window=_WINDOW, x_len=_X_LEN, window_share=0.956,
            max_chunks=_GROUP_CHUNKS)
        return ops.wsell_matvec(dev, x, backend="auto")
    compiled = _compile(
        f, on_chip, ((_TOTAL + _REM_TAIL, _B_R), vdt),
        ((_TOTAL, _B_R), jnp.int16), ((_TOTAL // _CHUNK_L,), jnp.int32),
        ((_N_BLOCKS,), jnp.int32), ((_N_OUT,), jnp.int32),
        # 3.4M columns need int32 remainder columns under either policy;
        # the window offsets are int16 under both
        ((_N_REM,), jnp.int32), ((_N_REM,), jnp.int32), ((_N,), jnp.float32))
    # the in-window slots gather nothing in XLA: no gathered stream
    text = compiled.as_text()
    assert "wsell_spmv" in text
    assert f"f32[{_TOTAL * _B_R}]" not in text
    assert f"f32[{_TOTAL},{_B_R}]" not in text
    # y leaves the kernel in original row order: the program ends in the
    # kernel's output slice, and no XLA gather makes a y after it
    root = _entry_root(text)
    assert re.search(r"\(%wsell_spmv[\w.]*\)", root), root
    assert not [line for line in text.splitlines()
                if f"= f32[{_N_PAD}]" in line and "gather" in line]


@pytest.mark.parametrize("vdt,idt", _POLICIES)
def test_fused_iter_compiles(on_chip, vdt, idt):
    from repro.kernels.fused_iter import fused_matvec_dots

    def f(val, col, cm, inv, x, w1, w2):
        return fused_matvec_dots(_sell(val, col, cm, inv), x, w1, w2,
                                 backend=ops.resolve_backend("auto"))
    vec = ((_N_PAD,), jnp.float32)
    _compile(f, on_chip, *_blocked_shapes(vdt, idt),
             ((_N_PAD,), jnp.int32), vec, vec, vec)


@pytest.mark.parametrize("vdt,idt", _POLICIES)
def test_ellr_spmv_compiles(on_chip, vdt, idt):
    def f(val, col, rowlen, tc, x):
        dev = ops.ELLDevice(val=val, col_idx=col, rowlen=rowlen,
                            tile_chunks=tc, chunk_l=_CHUNK_L, tile_r=_B_R)
        return ops.ell_matvec(dev, x, backend="auto")
    _compile(f, on_chip, ((_ELL_DEPTH, _N_PAD), vdt),
             ((_ELL_DEPTH, _N_PAD), idt), ((_N_PAD,), jnp.int32),
             ((_N_BLOCKS,), jnp.int32), ((_N,), jnp.float32))


@pytest.mark.parametrize("vdt,idt", _POLICIES)
def test_cmrs_spmv_compiles(on_chip, vdt, idt):
    def f(val, col, ris, cm, x):
        dev = ops.CMRSDevice(val=val, col_idx=col, row_in_strip=ris,
                             chunk_map=cm, strip_map=cm, n_strips=_N_BLOCKS,
                             b_r=_B_R, chunk_l=_CHUNK_L,
                             max_chunks=_GROUP_CHUNKS)
        return ops.cmrs_matvec(dev, x, backend="auto")
    val, col, cm = _blocked_shapes(vdt, idt)
    _compile(f, on_chip, val, col, ((_TOTAL, _B_R), jnp.int8), cm,
             ((_N,), jnp.float32))


@pytest.mark.parametrize("vdt,idt", _POLICIES)
def test_pjds_spmm_compiles(on_chip, vdt, idt):
    def f(val, col, cm, x):
        return ops.pjds_matmat(_pjds(val, col, cm), x, backend="auto")
    _compile(f, on_chip, *_blocked_shapes(vdt, idt),
             ((_N, _N_RHS), jnp.float32))


def test_auto_backend_resolves_to_compiled_kernels(on_chip):
    # what the compile tests lean on: on a TPU the dispatch defaults
    # pick the Pallas kernels, compiled (not interpreted)
    assert ops.resolve_backend("auto") == "kernel"
    assert ops.resolve_interpret(None) is False


# Graph500 scale 22 as the graph500.pagerank cell draws it: 2,395,575
# live vertices; P stored as CMRS in 1,152,976 rows of 128 slots
# (147.6M slots for 128.3M non-zeros), one strip group 116 chunks deep.
_G500_N = 2_395_575
_G500_ROWS = 1_152_976
_G500_GROUP_CHUNKS = 116
_HLO_LINE = re.compile(r"^\s*(?:ROOT )?(%[\w.\-]+) = (\S+) [\w\-]+\(([^)]*)\)")


def test_pagerank_gathers_x_from_vmem(on_chip):
    """The gathered formats' RHS gather reads a copy of x padded to whole
    tiles, and XLA:TPU places that copy in VMEM (memory space S(1)): at
    graph500-22 a gather from x in HBM took 15.6 ns a slot on a v5e
    against 8.6, the price ``select_format`` gives every gathered slot."""
    from repro.core import graph as G
    from repro.core.operator import DeviceOperator

    def f(val, col, ris, cm, sm, dangling, x):
        dev = ops.CMRSDevice(val=val, col_idx=col, row_in_strip=ris,
                             chunk_map=cm, strip_map=sm,
                             n_strips=-(-_G500_N // _B_R), b_r=_B_R,
                             chunk_l=_CHUNK_L,
                             max_chunks=_G500_GROUP_CHUNKS)
        op = DeviceOperator(ops.SparseDevice(
            fmt="cmrs", shape=(_G500_N, _G500_N), dev=dev))
        return G.pagerank_steps(G.PageRankOperator(op=op, dangling=dangling),
                                x, 1)
    compiled = _compile(
        f, on_chip, ((_G500_ROWS, _B_R), jnp.float32),
        ((_G500_ROWS, _B_R), jnp.int32), ((_G500_ROWS, _B_R), jnp.int8),
        ((_G500_ROWS // _CHUNK_L,), jnp.int32), ((_G500_ROWS,), jnp.int32),
        ((_G500_N,), jnp.bool_), ((_G500_N,), jnp.float32))
    shape, operands = {}, {}
    for line in compiled.as_text().splitlines():
        hit = _HLO_LINE.match(line)
        if hit:
            shape[hit[1]] = hit[2]
            if "repro.gather_rhs/gather" in line:
                operands[hit[1]] = [o.strip() for o in hit[3].split(",")]
    x_pad = -(-_G500_N // (OUT_BLOCKS * LANES)) * OUT_BLOCKS * LANES
    gathers = [args for name, args in operands.items()
               if shape[name].startswith(f"f32[{_G500_ROWS * _B_R}]")]
    assert gathers, "no gather of the slots under repro.gather_rhs"
    xs = [o for args in gathers for o in args
          if shape.get(o, "").startswith((f"f32[{_G500_N}]",
                                          f"f32[{x_pad}]"))]
    assert xs, "the gather reads no x"
    assert all("S(1)" in shape[o] for o in xs), [shape[o] for o in xs]


def test_cmrs_kernel_takes_no_epilogue(on_chip):
    """The grouped grid's row-order epilogue is opt-in: at graph500-22's
    shapes the CMRS kernel takes its three scalar-prefetched extents and
    its three streams (values, gathered x, row routing), nothing more."""
    def f(val, col, ris, cm, x):
        dev = ops.CMRSDevice(val=val, col_idx=col, row_in_strip=ris,
                             chunk_map=cm, strip_map=cm,
                             n_strips=-(-_G500_N // _B_R), b_r=_B_R,
                             chunk_l=_CHUNK_L,
                             max_chunks=_G500_GROUP_CHUNKS)
        return ops.cmrs_matvec(dev, x, backend="auto")
    compiled = _compile(
        f, on_chip, ((_G500_ROWS, _B_R), jnp.float32),
        ((_G500_ROWS, _B_R), jnp.int32), ((_G500_ROWS, _B_R), jnp.int8),
        ((_G500_ROWS // _CHUNK_L,), jnp.int32), ((_G500_N,), jnp.float32))
    assert len(_kernel_operands(compiled.as_text(), "cmrs_spmv")) == 6

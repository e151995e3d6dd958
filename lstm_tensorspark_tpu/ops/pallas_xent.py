"""The dense LM head's two Pallas kernels: every reduction over the ``[N, V]``
logits is made while a matmul kernel holds the tile in VMEM.

``lm_head_fwd``  grid (row tiles, V tiles), V the inner, sequential axis.
    ``z = ys · W + b`` on the MXU (bf16 inputs, float32 accumulation),
    stored in the logits' dtype, and on the stored values upcast to float32
    a running max and sum per row (the online logsumexp of
    `xent.chunked_xent_mean`) and the target's logit (a lane iota against
    the target). At the last V tile: ``lse`` and the target logit.
``lm_head_dx``   grid (row tiles, V tiles), V the reduction axis.
    ``dlog = (exp(z - lse) - onehot) · g/N`` in float32 in VMEM; its column
    sums are the row tile's share of the bias gradient (``[N/rows, 1, V]``
    float32, summed by the caller), then it is rounded once to the logits'
    dtype and ``dys += dlog · W`` accumulates in float32.

The head's weight gradient stays XLA's fusion (`xent._dense_bwd`): it runs
at 92-97% of the MXU's peak and forms dlogits in its own operand.

Both read the head vocabulary-major, ``[V, H]``: the TPU stores config 5's
``[H, V]`` head with H minor, so that view is free (`stored_vocab_major`
says where it is), and a tied head is the embedding itself. A float32 head
is cast to bf16 a tile at a time in VMEM.
Each tile runs as a loop over steps of ``sub`` rows, ``UNROLL`` steps an
iteration, so that the compiler overlaps one step's element-wise work with
the next step's matmul; a tile's code grows with the steps written out, and
so does the kernel's compile time, which every run of a new program pays.

V need not be a multiple of the V tile: the last tile is ragged, its reads
past V are whatever the buffer held, so those columns are -inf in the
logsumexp, 0 in dlogits and 0 in the head's tile (0 · NaN would poison
``dys``); writes past V are dropped. The head is never padded: a padded
copy costs a pass over it, and a padded parameter changes the checkpoint.

`plan` says whether a call can take the kernels and with which tiles;
`ops/xent.py::dense_xent_mean` runs XLA's operations wherever it says no.
Exactness: tests/test_xent.py (interpreted); the compiled step:
tests/test_chip_compile.py; the tiles' times: tools/lm_head_probe.py.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128


class Tiles(NamedTuple):
    rows: int  # rows a tile
    cols: int  # vocabulary columns a tile
    sub: int  # rows a step of the tile's loop


# chosen on the chip at config 5's shape (N 8192, H 1024, V 50,000; PERF.md
# section 6): the forward 4.99 ms at (2048, 1024, 256), the dx 4.81 ms
# at (1024, 2048, 128), two steps written out an iteration. Every step
# written out (eight) gave 4.50 / 4.52 ms but 7 s more compile a program
# (+24% `setup_s`: the train cell's reference check compiles anew each run);
# one step 5.64 / 5.52, four 4.72 / 4.70 at 2 s more. Rows fall to the
# largest power of two dividing N
FWD_TILES = Tiles(2048, 1024, 256)
DX_TILES = Tiles(1024, 2048, 128)
UNROLL = 2
MIN_ROWS = 16  # a bf16 tile's sublanes
_VMEM_LIMIT = 96 * 2 ** 20
_CONTRACT_LAST = (((1,), (1,)), ((), ()))  # x · yᵀ


class Plan(NamedTuple):
    fwd: Tiles
    dx: Tiles
    interpret: bool = False


def _vmem_bytes(t: Tiles, h: int) -> int:
    """What a kernel holds in VMEM at the widest dtypes (float32 hidden
    states, head and logits), the larger of the two kernels: two buffers of
    each block, the dx accumulator, the head's bf16 tile, a step's
    temporaries."""
    rows_h, cols_h, tile = t.rows * h * 4, t.cols * h * 4, t.rows * t.cols * 4
    common = 4 * t.sub * t.cols * 4 + t.cols * h * 2
    return common + max(2 * rows_h + 2 * cols_h + 2 * tile,  # forward
                        2 * tile + 2 * cols_h + 3 * rows_h)  # dx


def _fit(t: Tiles, n: int, h: int, v: int) -> Tiles | None:
    """``t`` cut to the problem: rows halved until they divide ``n``,
    columns no wider than V, then rows and columns halved until the kernel
    fits its VMEM (a width of 4,096 does not fit at config 5's tiles)."""
    rows, cols = t.rows, min(t.cols, pl.cdiv(v, LANE) * LANE)
    while rows >= MIN_ROWS and n % rows:
        rows //= 2
    while rows >= MIN_ROWS and _vmem_bytes(
            Tiles(rows, cols, min(t.sub, rows)), h) > _VMEM_LIMIT:
        if cols > LANE and cols >= rows:
            cols //= 2
        else:
            rows //= 2
    if rows < MIN_ROWS:
        return None
    return Tiles(rows, cols, min(t.sub, rows))


def _mesh_rule(h: int, v: int, head_dtype) -> bool:
    """Whether the mesh around the call lets the kernels run, read once.
    Not where the compiler partitions an axis of it: Mosaic lowers a
    ``pallas_call`` only where every axis of the mesh is manual, whatever
    the sizes of the automatic ones, so a ``shard_map`` that leaves an
    axis automatic (the sequence-, pipeline- and tensor-parallel LM steps
    without ``use_pallas``) keeps XLA's operations. Nor where the head
    arrives gathered: the data-parallel step keeps a head that
    `train.sharded_update.shard_dim` shards over its data axis as a share
    and gathers it for the step, and there XLA's operations are faster
    (config 5 on four chips: the weight-gradient fusion that follows the
    kernels read their logits at 5.80 ms a step against 4.39 after XLA's
    own, and head + loss took 15.80 ms against 15.50; PERF.md section 6).
    Elsewhere (no mesh, one device, the replicated head of the sequence or
    pipeline step with ``use_pallas``) the head is read as stored.

    A ``jit`` that GSPMD partitions with no ``shard_map`` around the call
    shows no mesh here: no LM step runs the head that way."""
    mesh = jax.sharding.get_abstract_mesh()
    manual = set(getattr(mesh, "manual_axes", ()))
    if any(name not in manual for name in mesh.axis_names):
        return False
    spread = {a for a in manual if mesh.shape[a] > 1}
    if spread != {"data"}:
        return True
    from ..train.sharded_update import shard_dim

    return shard_dim((h, v), jnp.dtype(head_dtype).itemsize,
                     mesh.shape["data"]) is None


def stored_vocab_major(h: int, v: int) -> bool:
    """Whether the TPU stores an ``[H, V]`` head with H minor, which makes
    the kernels' ``[V, H]`` view of it free. Its compiler lays a 2-D array
    out whichever way pads it less to the (8, 128) tile, row-major on a tie:
    f32[1024, 50000] is stored [50000][1024], f32[1024, 32000] as it is
    (tests/test_chip_compile.py holds the compiled step to no copy)."""
    def pad(x, m):
        return -(-x // m) * m
    return pad(v, 8) * pad(h, LANE) < pad(h, 8) * pad(v, LANE)


def plan(n: int, h: int, v: int, ldtype, *, head_dtype=jnp.float32,
         platform: str | None = None) -> Plan | None:
    """Tiles for ``n`` rows of width ``h`` against ``v`` classes and a head
    of ``head_dtype``, or None where the call keeps XLA's operations:
    another backend than the TPU, a width off the MXU's 128 lanes, a head
    stored row-major (its ``[V, H]`` view would be a copy of the head each
    step), logits neither bf16 nor float32, rows that no row tile divides,
    an automatic mesh axis around the call, or a head the data-parallel
    step gathers (`_mesh_rule`)."""
    if platform is None:
        platform = jax.default_backend()
    if (platform != "tpu" or h % LANE or not stored_vocab_major(h, v)
            or jnp.dtype(ldtype) not in (jnp.bfloat16, jnp.float32)
            or not _mesh_rule(h, v, head_dtype)):
        return None
    fwd, dx = _fit(FWD_TILES, n, h, v), _fit(DX_TILES, n, h, v)
    return None if fwd is None or dx is None else Plan(fwd, dx)


def _columns(shape, j, tv):
    return lax.broadcasted_iota(jnp.int32, shape, 1) + j * tv


def _steps(n, body, carry):
    """``body(i, carry)`` for i < n, ``UNROLL`` steps an iteration of a
    loop (the body's code is written out that many times)."""
    k = UNROLL if n % UNROLL == 0 else 1

    def some(i, c):
        for j in range(k):
            c = body(i * k + j, c)
        return c

    return lax.fori_loop(0, n // k, some, carry)


def _fwd_kernel(ys_ref, w_ref, b_ref, tgt_ref, z_ref, lse_ref, tl_ref,
                m_s, s_s, t_s, *, tv, vocab, sub):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        m_s[...] = jnp.full(m_s.shape, -jnp.inf, jnp.float32)
        s_s[...] = jnp.zeros(s_s.shape, jnp.float32)
        t_s[...] = jnp.zeros(t_s.shape, jnp.float32)

    ldtype = z_ref.dtype
    wt = w_ref[...].astype(jnp.bfloat16)
    bias = b_ref[...].astype(ldtype).astype(jnp.float32)

    # rows in steps of `sub` (the module's docstring says why)
    def step(i, carry):
        rows = pl.ds(pl.multiple_of(i * sub, sub), sub)
        acc = lax.dot_general(ys_ref[rows, :].astype(jnp.bfloat16), wt,
                              _CONTRACT_LAST,
                              preferred_element_type=jnp.float32)
        # the stored logits: the product rounded to their dtype, plus the
        # bias in their dtype (XLA's `dot(..., preferred_element_type) + b`)
        z = (acc.astype(ldtype).astype(jnp.float32) + bias).astype(ldtype)
        z_ref[rows, :] = z
        zf = z.astype(jnp.float32)
        col = _columns(zf.shape, j, tv)
        if vocab % tv:
            zf = jnp.where(col < vocab, zf, -jnp.inf)
        m_old = m_s[rows, :]
        m_new = jnp.maximum(m_old, jnp.max(zf, axis=1, keepdims=True))
        s_s[rows, :] = (s_s[rows, :] * jnp.exp(m_old - m_new)
                        + jnp.sum(jnp.exp(zf - m_new), axis=1, keepdims=True))
        m_s[rows, :] = m_new
        t_s[rows, :] += jnp.sum(jnp.where(col == tgt_ref[rows, :], zf, 0.0),
                                axis=1, keepdims=True)
        return carry

    _steps(ys_ref.shape[0] // sub, step, None)

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        lse_ref[...] = m_s[...] + jnp.log(s_s[...])
        tl_ref[...] = t_s[...]


def lm_head_fwd(ys2d, wt, bias, tgt, ldtype, p: Plan):
    """``ys2d [N, H]``, ``wt [V, H]`` (the head vocabulary-major; bf16, or
    float32 cast a tile at a time), ``bias [V]``, ``tgt [N]`` int ->
    (logits ``[N, V]`` in ``ldtype``, lse ``[N]`` float32, target logit
    ``[N]`` float32)."""
    n, h = ys2d.shape
    v = wt.shape[0]
    t = p.fwd
    rows = pl.BlockSpec((t.rows, 1), lambda i, j: (i, 0))
    logits, lse, tl = pl.pallas_call(
        lambda *refs: _fwd_kernel(*refs, tv=t.cols, vocab=v, sub=t.sub),
        grid=(n // t.rows, pl.cdiv(v, t.cols)),
        in_specs=[
            pl.BlockSpec((t.rows, h), lambda i, j: (i, 0)),
            pl.BlockSpec((t.cols, h), lambda i, j: (j, 0)),
            pl.BlockSpec((1, t.cols), lambda i, j: (0, j)),
            rows,
        ],
        out_specs=[pl.BlockSpec((t.rows, t.cols), lambda i, j: (i, j)), rows,
                   rows],
        out_shape=[jax.ShapeDtypeStruct((n, v), ldtype),
                   jax.ShapeDtypeStruct((n, 1), jnp.float32),
                   jax.ShapeDtypeStruct((n, 1), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((t.rows, 1), jnp.float32)] * 3,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=p.interpret, name="lm_head_fwd",
    )(ys2d, wt, bias.reshape(1, v), tgt.reshape(n, 1).astype(jnp.int32))
    return logits, lse[:, 0], tl[:, 0]


def _dx_kernel(gn_ref, z_ref, lse_ref, tgt_ref, w_ref, dys_ref, db_ref,
               acc_s, *, tv, vocab, sub):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        acc_s[...] = jnp.zeros(acc_s.shape, jnp.float32)

    def accumulate(ragged):
        wt = w_ref[...].astype(jnp.bfloat16)
        if ragged:
            vrow = lax.broadcasted_iota(jnp.int32, wt.shape, 0) + j * tv
            wt = jnp.where(vrow < vocab, wt, 0)
        def step(i, db):  # in steps of rows, as the forward
            rows = pl.ds(pl.multiple_of(i * sub, sub), sub)
            zf = z_ref[rows, :].astype(jnp.float32)
            col = _columns(zf.shape, j, tv)
            onehot = (col == tgt_ref[rows, :]).astype(jnp.float32)
            dlog = (jnp.exp(zf - lse_ref[rows, :]) - onehot) * gn_ref[...]
            if ragged:
                dlog = jnp.where(col < vocab, dlog, 0.0)
            dl = dlog.astype(dys_ref.dtype).astype(jnp.bfloat16)
            acc_s[rows, :] += jnp.dot(dl, wt,
                                      preferred_element_type=jnp.float32)
            # the bias gradient from float32 dlogits, before the rounding
            return db + jnp.sum(dlog, axis=0, keepdims=True)

        db_ref[...] = _steps(z_ref.shape[0] // sub, step,
                             jnp.zeros(db_ref.shape, jnp.float32))

    # the last V tile alone masks its columns past V (garbage reads); the
    # full tiles pay for no mask
    if vocab % tv:
        last = pl.num_programs(1) - 1
        pl.when(j < last)(lambda: accumulate(False))
        pl.when(j == last)(lambda: accumulate(True))
    else:
        accumulate(False)

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        dys_ref[...] = acc_s[...].astype(dys_ref.dtype)


def lm_head_dx(logits, lse, tgt, wt, gn, p: Plan):
    """``logits [N, V]``, ``lse [N]``, ``tgt [N]``, ``wt [V, H]``, ``gn``
    the scalar g/N -> (``dys [N, H]`` in the logits' dtype, the bias
    gradient ``[V]`` float32)."""
    n, v = logits.shape
    h = wt.shape[1]
    t = p.dx
    rows = pl.BlockSpec((t.rows, 1), lambda i, j: (i, 0))
    dys, db = pl.pallas_call(
        lambda *refs: _dx_kernel(*refs, tv=t.cols, vocab=v, sub=t.sub),
        grid=(n // t.rows, pl.cdiv(v, t.cols)),
        in_specs=[
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
            pl.BlockSpec((t.rows, t.cols), lambda i, j: (i, j)),
            rows,
            rows,
            pl.BlockSpec((t.cols, h), lambda i, j: (j, 0)),
        ],
        out_specs=[pl.BlockSpec((t.rows, h), lambda i, j: (i, 0)),
                   pl.BlockSpec((None, 1, t.cols), lambda i, j: (i, 0, j))],
        out_shape=[jax.ShapeDtypeStruct((n, h), logits.dtype),
                   jax.ShapeDtypeStruct((n // t.rows, 1, v), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((t.rows, h), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=p.interpret, name="lm_head_dx",
    )(jnp.reshape(gn, (1, 1)).astype(jnp.float32), logits,
      lse.reshape(n, 1), tgt.reshape(n, 1).astype(jnp.int32), wt)
    return dys, jnp.sum(db, axis=(0, 1))

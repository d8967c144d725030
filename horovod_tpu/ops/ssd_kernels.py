"""Pallas TPU kernels for the chunked state-space scan
(``models/state_space.py`` has the recurrence, the chunked form's
mathematics and the XLA form).

A chunk of ``Q`` steps of ONE GROUP of heads a grid step, the chunk axis
innermost and sequential: the group's state (``[K P, N]`` float32 for
``K`` heads of ``P`` channels) lives in a VMEM scratch, and the decay
matrix ``L``, ``C B^T`` and their product never leave VMEM.  A pass reads
``x``, ``B``, ``C`` and the scalars of a step once and writes ``y`` (the
backward pass: four gradients) once.

**The scalars** (``cum``, the running sum of ``dt A`` inside a chunk, and
``dt``) come as ``[B, G, chunks, R, Q]`` with the steps on lanes: rows
``0 .. K-1`` are the heads' ``cum``, rows ``K .. 2K-1`` their ``dt``, the
rest zeros up to a multiple of 8.  A row is what ``L[t, s] = exp(cum_t -
cum_s)`` needs along ``s``; what it needs along ``t``, and what scales a
row of ``x``, is a column, and one transposition of the rows (padded to 128
of them) gives every column at once.  The backward kernel collects its
per-step sums as columns and transposes them back the same way.

**Heads narrower than the 128 lanes** (``P`` = 64: two heads a lane tile)
are never cut out of a tile.  The products that two heads share run on the
tile with the other head's lanes zeroed in one operand, stacked along the
contraction: ``Y = [M_a | M_b] [U_a ; U_b]`` with ``U_a`` the tile's ``dt .
X`` under head ``a``'s lanes only.  The two products a group's heads share
in full (``C H^T`` and ``X^T B``) are one product each over the group's
channels.

Backward, with ``dY`` and the state's gradient ``dH`` carried in VMEM from
the chunk after (``M = (L . C B^T)``, ``U = dt . X``, ``W = e^{cum_end -
cum} . U``, ``E = e^{cum} . dY``):

    dU = M^T dY                  dM = dY U^T            dCB = sum_k L_k . dM_k
    d(dt . X) = dU + e^{cum_end - cum} . (B dH^T)
    dB = dCB^T C + W dH          dC = dCB B + E H
    dH' = e^{cum_end} dH + E^T C
    d dt_t = sum_p (x . d(dt . X))_t             dD = sum_t (dY . x)_t
    d cum_t = sum_p (dY . (Y - D x) - U . dU - W . (B dH^T))_t
              + [t = Q-1] (sum (W . (B dH^T)) + e^{cum_end} sum (H . dH))

The last line is every path of ``cum`` at once: as ``L``'s row index and in
``e^{cum}`` it scales what step ``t`` reads (so its gradient is ``dY_t .
Y_t``), as ``L``'s column index and in ``e^{cum_end - cum}`` it scales what
step ``s`` gives, and ``cum_end`` scales the whole state the chunk leaves.
So ``L``'s own gradient is never formed.  What a step gives and what the
later steps read of it cancel in ``A``'s gradient for every step before
both, so each side is computed from the products' operands as they were
rounded, and a quantity that enters twice is one number.

``dt``, ``cum``, every exponent and exponential, the states and all
accumulation are float32; every exponent is a difference taken and masked
before the exponential; the products take their operands in the
activations' dtype, as the XLA form's do.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from ..common import device, scopes
from .pallas_kernels import _sds

LANES = 128
_NN, _NT, _TN = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))


def _dot(a, b, dims=_NN):
    return lax.dot_general(a, b, (dims, ((), ())),
                           preferred_element_type=jnp.float32)


def takes(head_size: int, per_group: int, state_size: int,
          chunk: int) -> bool:
    """The shapes the kernels are written for: heads that fill a lane tile,
    halve or quarter it, a group's channels and the state whole lane tiles,
    a chunk of one or two lane tiles (``L`` is ``chunk^2`` float32 a head),
    and a group no wider than VMEM holds twice over with its state (1024
    channels: 5 MiB of blocks and scratch in the backward kernel)."""
    width = per_group * head_size
    return (head_size in (LANES // 4, LANES // 2, LANES)
            and width % LANES == 0 and width <= 1024
            and state_size % LANES == 0 and chunk in (LANES, 2 * LANES))


def scalar_rows(per_group: int) -> int:
    """Rows of a step's block of scalars: ``cum`` and ``dt`` of each head,
    filled up to whole sublane tiles."""
    return -(-2 * per_group // 8) * 8


def _columns(rows):
    """``[R, Q]`` -> ``[Q, 128]``: column ``j`` is row ``j``."""
    r, q = rows.shape
    return jnp.concatenate(
        [rows, jnp.zeros((LANES - r, q), rows.dtype)]).T


def _along_lanes(x, n):
    """``[1, 1]`` -> ``[1, n]``: Mosaic broadcasts along lanes and along
    sublanes in two steps, not in one."""
    return jnp.where(_lane((1, n)) >= 0, x, 0.0)


def _lane(shape):
    return lax.broadcasted_iota(jnp.int32, shape, 1)


def _spread(cols, first, heads, p, q):
    """``[Q, 128]`` whose lanes of the tile's head ``i`` hold column
    ``first + i`` of ``cols``."""
    out = cols[:, first + heads - 1:first + heads]
    lane = _lane((q, LANES))
    for i in range(heads - 2, -1, -1):
        out = jnp.where(lane < (i + 1) * p, cols[:, first + i:first + i + 1],
                        out)
    return jnp.broadcast_to(out, (q, LANES))


def _spread_row(values, heads, p):
    """``[1, 128]`` whose lanes of head ``i`` hold the ``[1, 1]``
    ``values[i]``."""
    out = _along_lanes(values[heads - 1], LANES)
    lane = _lane((1, LANES))
    for i in range(heads - 2, -1, -1):
        out = jnp.where(lane < (i + 1) * p, values[i], out)
    return out


def _own_lanes(x, i, heads, p):
    """``x`` ``[Q, 128]`` with every lane but head ``i``'s zeroed."""
    if heads == 1:
        return x
    lane = _lane(x.shape)
    return jnp.where((lane >= i * p) & (lane < (i + 1) * p), x,
                     jnp.zeros_like(x))


def _tile(rows, cols, x_ref, j, *, per, p, q):
    """What both kernels compute of lane tile ``j`` before they meet the
    state: ``x`` float32, ``dt`` and the two exponentials spread over the
    tile's heads' lanes, ``dt . X`` float32."""
    heads = LANES // p
    first = j * heads
    x = x_ref[0, :, j * LANES:(j + 1) * LANES].astype(jnp.float32)
    cum = _spread(cols, first, heads, p, q)
    dt = _spread(cols, per + first, heads, p, q)
    end = _spread_row([rows[first + i:first + i + 1, q - 1:q]
                       for i in range(heads)], heads, p)
    return x, dt, jnp.exp(cum), jnp.exp(end - cum), x * dt


def _masked(rows, cols, cb, seen, k):
    """Head ``k``'s ``L`` (float32) and ``L . C B^T``."""
    diff = cols[:, k:k + 1] - rows[k:k + 1, :]              # [t, s]
    decay = jnp.exp(jnp.where(seen, diff, -jnp.inf))
    return decay, decay * cb


def _seen(q):
    return lax.broadcasted_iota(jnp.int32, (q, q), 0) \
        >= lax.broadcasted_iota(jnp.int32, (q, q), 1)


def _decay_state(h, rows, *, per, p, q):
    """``h`` ``[K P, N]`` with each head's rows scaled by its
    ``exp(cum_end)``."""
    return jnp.concatenate([
        h[k * p:(k + 1) * p] * _along_lanes(
            jnp.exp(rows[k:k + 1, q - 1:q]), h.shape[1])
        for k in range(per)])


def _fwd_kernel(x_ref, scal_ref, b_ref, c_ref, d_ref, y_ref, starts_ref,
                h_scr, *, per: int, p: int):
    q = x_ref.shape[1]
    act = x_ref.dtype
    heads = LANES // p

    @pl.when(pl.program_id(2) == 0)
    def _():
        h_scr[:] = jnp.zeros_like(h_scr)

    # Under a condition like the start: the interpreter, run inside a
    # shard_map that checks what varies over the mesh, reads a varying
    # block at the grid's (unvarying) indices only inside one.
    @pl.when(pl.program_id(2) >= 0)
    def _():
        rows = scal_ref[0, 0, 0]
        cols = _columns(rows)
        b, c = b_ref[0], c_ref[0]
        cb = _dot(c, b, _NT)
        seen = _seen(q)
        h = h_scr[:]
        starts_ref[0, 0, 0] = h
        carried = _dot(c, h.astype(act), _NT)       # C H^T [Q, K P]
        to_end = []
        for j in range(per // heads):
            at = slice(j * LANES, (j + 1) * LANES)
            x, _, e_in, e_out, dtx = _tile(rows, cols, x_ref, j, per=per, p=p,
                                           q=q)
            u = dtx.astype(act)
            mixed = jnp.concatenate(
                [_masked(rows, cols, cb, seen, j * heads + i)[1].astype(act)
                 for i in range(heads)], axis=1)
            y_ref[0, :, at] = _dot(mixed, jnp.concatenate(
                [_own_lanes(u, i, heads, p) for i in range(heads)])) \
                + carried[:, at] * e_in + x * d_ref[:, at]
            to_end.append((dtx * e_out).astype(act))
        h_scr[:] = _decay_state(h, rows, per=per, p=p, q=q) \
            + _dot(jnp.concatenate(to_end, axis=1), b, _TN)


def _bwd_kernel(x_ref, scal_ref, b_ref, c_ref, d_ref, dy_ref, starts_ref,
                dx_ref, dscal_ref, db_ref, dc_ref, dd_ref, dh_scr, *,
                per: int, p: int):
    q = x_ref.shape[1]
    act = x_ref.dtype
    heads = LANES // p
    n_rows = scal_ref.shape[3]

    @pl.when(pl.program_id(2) == 0)
    def _():
        dh_scr[:] = jnp.zeros_like(dh_scr)
        dd_ref[...] = jnp.zeros_like(dd_ref)

    @pl.when(pl.program_id(2) >= 0)          # as in the forward kernel
    def _():
        rows = scal_ref[0, 0, 0]
        cols = _columns(rows)
        b, c = b_ref[0], c_ref[0]
        cb = _dot(c, b, _NT)
        seen = _seen(q)
        h = starts_ref[0, 0, 0]
        hb = h.astype(act)
        dh = dh_scr[:]
        dhb = dh.astype(act)
        carried = _dot(c, hb, _NT)                              # C H^T
        d_to_end = _dot(b, dhb, _NT)                            # B dH^T
        dcb = jnp.zeros((q, q), jnp.float32)
        sums = jnp.zeros((q, LANES), jnp.float32)
        lane = _lane((q, LANES))
        row = lax.broadcasted_iota(jnp.int32, (n_rows, q), 0)
        ends = jnp.zeros((n_rows, q), jnp.float32)
        to_end, read = [], []
        for j in range(per // heads):
            at = slice(j * LANES, (j + 1) * LANES)
            x, dt, e_in, e_out, dtx = _tile(rows, cols, x_ref, j, per=per, p=p,
                                            q=q)
            dy = dy_ref[0, :, at]
            dyb = dy.astype(act)
            u = dtx.astype(act)
            w = dtx * e_out
            decays, mixed = zip(*[_masked(rows, cols, cb, seen, j * heads + i)
                                  for i in range(heads)])
            mixed = jnp.concatenate([m.astype(act) for m in mixed], axis=1)
            own = jnp.concatenate([_own_lanes(u, i, heads, p)
                                   for i in range(heads)])
            core = _dot(mixed, own) + carried[:, at] * e_in     # y - D x
            du_all = _dot(mixed, dyb, _TN)          # [heads Q, 128]
            du = sum(_own_lanes(du_all[i * q:(i + 1) * q], i, heads, p)
                     for i in range(heads))
            dm = _dot(dyb, own, _NT)                            # [Q, heads Q]
            for i in range(heads):
                dcb = dcb + decays[i] * dm[:, i * q:(i + 1) * q]
            d_dtx = du + e_out * d_to_end[:, at]
            dx_ref[0, :, at] = (d_dtx * dt + dy * d_ref[:, at]).astype(act)
            # D's gradient: the block stays in VMEM over the chunks and
            # takes each chunk's sum over its steps, eight rows apart.
            skipped = dy * x
            dd_ref[0, 0, :, at] += sum(skipped[r:r + 8]
                                       for r in range(0, q, 8))
            # An exponent's gradient is ONE number that both its ends take, or
            # what should cancel between a step that gives and the steps that
            # read it is left standing at the operands' rounding: the products'
            # operands as they were rounded, and ``left`` summed for cum_end
            # from the same numbers that each step loses.
            left = w * d_to_end[:, at]
            d_cum = dyb.astype(jnp.float32) * core \
                - u.astype(jnp.float32) * du - left
            d_dt = x * d_dtx
            left_sum = jnp.sum(left, axis=0, keepdims=True)
            for i in range(heads):
                k = j * heads + i
                sums = jnp.where(
                    lane == k, jnp.sum(_own_lanes(d_cum, i, heads, p), axis=1,
                                       keepdims=True),
                    jnp.where(lane == per + k,
                              jnp.sum(_own_lanes(d_dt, i, heads, p), axis=1,
                                      keepdims=True), sums))
                # cum_end scales what the chunk leaves: the steps' own parts
                # and the state it started from.
                end = jnp.sum(_own_lanes(left_sum, i, heads, p), axis=1,
                              keepdims=True) \
                    + jnp.exp(rows[k:k + 1, q - 1:q]) * jnp.sum(
                        h[k * p:(k + 1) * p] * dh[k * p:(k + 1) * p],
                        keepdims=True)
                ends = jnp.where(row == k, _along_lanes(end, q), ends)
            to_end.append(w.astype(act))
            read.append((dy * e_in).astype(act))
        to_end = jnp.concatenate(to_end, axis=1)
        read = jnp.concatenate(read, axis=1)
        dcb = dcb.astype(act)
        dc_ref[0] = (_dot(dcb, b) + _dot(read, hb)).astype(act)
        db_ref[0] = (_dot(dcb, c, _TN) + _dot(to_end, dhb)).astype(act)
        dscal_ref[0, 0, 0] = sums.T[:n_rows] \
            + jnp.where(_lane((n_rows, q)) == q - 1, ends, 0.0)
        dh_scr[:] = _decay_state(dh, rows, per=per, p=p, q=q) \
            + _dot(read, c, _TN)


def _params(interpret):
    if interpret:
        return None
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))


def _specs(x, scal, b, n_chunks, backward):
    """Block specs of (a ``[B, S, K P]`` row block, the scalars, a ``[B, S,
    N]`` row block, ``D``'s row, the start states) on the grid (batch,
    group, chunk), the chunks last to first where ``backward``."""
    q = scal.shape[-1]
    width = x.shape[2] // scal.shape[1]
    n = b.shape[2] // scal.shape[1]

    def at(t):
        return n_chunks - 1 - t if backward else t

    return (pl.BlockSpec((1, q, width), lambda i, g, t: (i, at(t), g)),
            pl.BlockSpec((1, 1, 1) + scal.shape[3:],
                         lambda i, g, t: (i, g, at(t), 0, 0)),
            pl.BlockSpec((1, q, n), lambda i, g, t: (i, at(t), g)),
            pl.BlockSpec((1, width), lambda i, g, t: (0, g)),
            pl.BlockSpec((1, 1, 1, width, n),
                         lambda i, g, t: (i, g, at(t), 0, 0)))


@jax.named_scope(scopes.SSD_FWD)
def forward(x, scal, b, c, d_row, p):
    """``x`` ``[B, S, H P]``, ``scal`` ``[B, G, chunks, R, Q]`` (module
    docstring), ``b``, ``c`` ``[B, S, G N]``, ``d_row`` ``[1, H P]`` float32
    (``D`` a channel) -> (``y`` ``[B, S, H P]`` float32, the state every
    chunk starts from ``[B, G, chunks, K P, N]`` float32)."""
    from jax.experimental.pallas import tpu as pltpu
    bsz, s, width = x.shape
    groups, n_chunks = scal.shape[1:3]
    per = width // p // groups
    interpret = not device.on_tpu()
    row, scalars, state_row, skip, states = _specs(x, scal, b, n_chunks,
                                                   False)
    starts = (bsz, groups, n_chunks, width // groups, b.shape[2] // groups)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, per=per, p=p),
        grid=(bsz, groups, n_chunks),
        in_specs=[row, scalars, state_row, state_row, skip],
        out_specs=[row, states],
        out_shape=[_sds((bsz, s, width), jnp.float32, x),
                   _sds(starts, jnp.float32, x)],
        scratch_shapes=[pltpu.VMEM(starts[3:], jnp.float32)],
        compiler_params=_params(interpret),
        interpret=interpret,
        name=scopes.kernel_name(scopes.SSD_FWD),
    )(x, scal, b, c, d_row)


@jax.named_scope(scopes.SSD_BWD)
def backward(x, scal, b, c, d_row, dy, starts, p):
    """Gradients of ``x``, ``scal``, ``b``, ``c`` in their shapes and
    dtypes (``b``'s and ``c``'s summed over a group's heads), and ``D``'s
    a channel as ``[B, G, 8, K P]`` partial sums over the steps (float32:
    the caller adds the ``8 B`` rows of a channel)."""
    from jax.experimental.pallas import tpu as pltpu
    bsz, s, width = x.shape
    groups, n_chunks = scal.shape[1:3]
    per = width // p // groups
    interpret = not device.on_tpu()
    row, scalars, state_row, skip, states = _specs(x, scal, b, n_chunks,
                                                   True)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, per=per, p=p),
        grid=(bsz, groups, n_chunks),
        in_specs=[row, scalars, state_row, state_row, skip, row, states],
        out_specs=[row, scalars, state_row, state_row,
                   pl.BlockSpec((1, 1, 8, width // groups),
                                lambda i, g, t: (i, g, 0, 0))],
        out_shape=[_sds(x.shape, x.dtype, x),
                   _sds(scal.shape, jnp.float32, x),
                   _sds(b.shape, b.dtype, x), _sds(c.shape, c.dtype, x),
                   _sds((bsz, groups, 8, width // groups), jnp.float32, x)],
        scratch_shapes=[pltpu.VMEM(starts.shape[3:], jnp.float32)],
        compiler_params=_params(interpret),
        interpret=interpret,
        name=scopes.kernel_name(scopes.SSD_BWD),
    )(x, scal, b, c, d_row, dy, starts)

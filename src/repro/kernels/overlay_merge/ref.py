"""Pure-jnp oracle for the overlay-merge kernel: identical plane semantics
(lexicographic biased-plane compares, rank arithmetic, -1/drop sentinels),
realized with gather-free broadcasting, prefix sums and a scatter instead of
the kernel's chunked rank pass and tiled one-hot extraction."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .overlay_merge import PAD, _lt


def _merge_ref_flat(akh, akl, aph, apl, atb,
                    bkh, bkl, bph, bpl, btb, *, cap_out: int):
    la = ~((akh == PAD) & (akl == PAD))
    lb = ~((bkh == PAD) & (bkl == PAD))
    eq = (akh[:, None] == bkh[None, :]) & (akl[:, None] == bkl[None, :])
    in_b = jnp.any(eq & lb[None, :], axis=1)
    surv = la & ~in_b
    blt = _lt(bkh[None, :], bkl[None, :], akh[:, None], akl[:, None])
    nb_lt = jnp.sum((blt & lb[None, :]).astype(jnp.int32), axis=1)
    surv_i = surv.astype(jnp.int32)
    rank_a = jnp.cumsum(surv_i) - surv_i
    pos_a = jnp.where(surv, rank_a + nb_lt, cap_out)   # out-of-range drops
    alt = _lt(akh[None, :], akl[None, :], bkh[:, None], bkl[:, None])
    na_lt = jnp.sum((alt & surv[None, :]).astype(jnp.int32), axis=1)
    lb_i = lb.astype(jnp.int32)
    rank_b = jnp.cumsum(lb_i) - lb_i
    pos_b = jnp.where(lb, rank_b + na_lt, cap_out)

    def scat(fill, va, vb, dtype):
        out = jnp.full((cap_out,), fill, dtype=dtype)
        return (out.at[pos_a].set(va, mode="drop")
                .at[pos_b].set(vb, mode="drop"))

    return (scat(PAD, akh, bkh, jnp.int32),
            scat(PAD, akl, bkl, jnp.int32),
            scat(0, aph, bph, jnp.int32),
            scat(0, apl, bpl, jnp.int32),
            scat(0, atb, btb, jnp.int32))


@functools.partial(jax.jit, static_argnames=("cap_out",))
def overlay_merge_ref(akh, akl, aph, apl, atb,
                      bkh, bkl, bph, bpl, btb, *, cap_out: int):
    """Stacked (S, ·) plane merge — same signature/returns as
    ``overlay_merge_planes`` minus the interpret switch."""
    fn = functools.partial(_merge_ref_flat, cap_out=cap_out)
    return jax.vmap(fn)(akh, akl, aph, apl, atb, bkh, bkl, bph, bpl, btb)

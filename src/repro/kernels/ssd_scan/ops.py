"""Public wrapper assembling the full SSD from the Pallas intra-chunk kernel
plus the (tiny) inter-chunk recurrence done in jnp."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .kernel import ssd_intra_pallas


@functools.partial(jax.jit, static_argnames=("chunk",))
def ssd_scan(x, dt, A, Bm, Cm, *, chunk: int, initial_state=None):
    """Same contract as ssd_reference: x (B,S,H,P), dt (B,S,H), A (H,),
    Bm/Cm (B,S,G,N) -> (y (B,S,H,P) f32, final_state (B,H,P,N) f32)."""
    B, S, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    nc = S // chunk
    assert nc * chunk == S

    xr = x.reshape(B, nc, chunk, H, P).transpose(0, 3, 1, 2, 4)
    dtr = dt.reshape(B, nc, chunk, H).transpose(0, 3, 1, 2)[..., None]
    Br = Bm.reshape(B, nc, chunk, G, N).transpose(0, 3, 1, 2, 4)
    Cr = Cm.reshape(B, nc, chunk, G, N).transpose(0, 3, 1, 2, 4)

    y_intra, states, cs = ssd_intra_pallas(
        xr.astype(jnp.float32), dtr.astype(jnp.float32), A.astype(jnp.float32),
        Br.astype(jnp.float32), Cr.astype(jnp.float32))

    cs = cs[..., 0]                                  # (B,H,nc,Q)
    chunk_decay = jnp.exp(cs[..., -1])               # (B,H,nc)
    h0 = (jnp.zeros((B, H, P, N), jnp.float32) if initial_state is None
          else initial_state.astype(jnp.float32))

    def step(h, inp):
        dec, st = inp                                # (B,H), (B,H,P,N)
        return h * dec[..., None, None] + st, h

    dec_t = jnp.moveaxis(chunk_decay, 2, 0)          # (nc,B,H)
    st_t = jnp.moveaxis(states, 2, 0)                # (nc,B,H,P,N)
    h_final, h_starts = jax.lax.scan(step, h0, (dec_t, st_t))
    h_starts = jnp.moveaxis(h_starts, 0, 2)          # (B,H,nc,P,N)

    Ch = jnp.repeat(Cr.astype(jnp.float32), H // G, axis=1)  # (B,H,nc,Q,N)
    y_inter = jnp.einsum("bhcqn,bhcpn,bhcq->bhcqp", Ch, h_starts, jnp.exp(cs))

    y = (y_intra + y_inter).transpose(0, 2, 3, 1, 4).reshape(B, S, H, P)
    return y, h_final

"""The recurrent mixers of `HybridLM`: three implementations behind one seam.

A mixer owns what a recurrent layer adds to the block — its weights, its
projection, its causal convolution, the sequence form of its recurrence
(prefill), one step of it (decode) — and STATES WHICH MEMBERS of the serving
cache it keeps a slot a layer: a float32 state page (`state_shape`; None for
a mixer that has no recurrent state, and the cache then has no such member)
and the last `kernel - 1` convolution inputs (`conv_shape`). `HybridLM` asks

    shapes(i)                       the layer's weights, name -> shape
    ONES                            the leaves `init_params` sets to one
    state_shape, conv_shape         one slot's page of each member it keeps
    seq(params, i, u, length)       -> (out [L, D], state page, conv tail)
    step(params, i, u, state, conv, page, alive)
                                    -> (out [S, D], state slab, conv slab)
    kernel(slab_shape, dtype)       whether `step` takes the Pallas kernel

and nothing else: the norms around the mixer, the residual and the cache
plumbing are the block's. A mixer without a state takes and returns None
where the others take and return theirs.

* :class:`Mamba2Mixer` (`layer_types` "mamba") — module docstring of
  `hybrid.py`; state `[heads, head_dim, d_state]`.
* :class:`GatedDeltaMixer` ("linear_attention") — the gated delta rule
  (Gated DeltaNet, arXiv:2412.06464): a head's matrix state `S [dk, dv]`
  moves by `S' = a S; u = v - S'^T k; S = S' + b k u^T; o = S^T q` — the
  state is read to form its own correction, so a step is two contractions
  and a rank-one update, and a chunk of the prefill needs a
  unit-lower-triangular solve (the WY form). The state page is `[dk, heads *
  dv]`: the heads side by side on the lanes, so that a page's bytes on the
  chip are its count (a `[.., dk, dv]` page with `dv` 192 would be padded to
  256 lanes).
* :class:`ShortConvMixer` ("conv") — the gated short convolution of LFM2:
  `[B, C, x] = u W_in`; `y = C * conv(B * x)`, a causal depthwise
  convolution over time of `conv_L_cache` taps a channel, no bias, no
  activation; `out = y W_out`. NO recurrent state: what a decode step needs
  of the past is the window alone, the last `conv_L_cache - 1` values of `B
  * x` a channel.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from .. import telemetry

__all__ = ["Mamba2Mixer", "GatedDeltaMixer", "ShortConvMixer",
           "MIXERS"]


def _softplus_inverse_steps(key, shape):
    """`dt_bias = softplus^-1(log-uniform[1e-3, 1e-1])`: the Mamba-2
    reference initialisation of the step sizes."""
    step = jnp.exp(jax.random.uniform(
        key, shape, jnp.float32, np.log(1e-3), np.log(1e-1)))
    return step + jnp.log(-jnp.expm1(-step))


def _kernels_enabled(mesh):
    from ..ops import pallas_attention as pa

    return mesh.size == 1 and pa.pallas_enabled()


def _conv_seq(x, w, bias, length):
    """The causal depthwise convolution of one whole sequence `x` [L, C]
    under `w` [kernel, C] (and `bias` [C] or None), float32: `(conv [L, C],
    tail [kernel - 1, C])` — the tail is the last `kernel - 1` inputs up to
    token `length - 1` (zeros before the sequence's start), what a decode
    step's window starts from."""
    k, L = w.shape[0], x.shape[0]
    padded = jnp.concatenate(
        [jnp.zeros((k - 1, x.shape[1]), x.dtype), x], axis=0)
    # padded row t + k - 1 is token t: the tail is tokens
    # [length - (k - 1), length)
    tail = lax.dynamic_slice_in_dim(padded, length, k - 1, axis=0)
    w = w.astype(jnp.float32)
    if bias is not None:
        bias = bias.astype(jnp.float32)
    conv = sum(padded[j:j + L].astype(jnp.float32) * w[j] for j in range(k))
    return (conv if bias is None else bias + conv), tail


def _conv_step(conv, page, x, w, bias, alive):
    """One token a slot through the convolution: `conv` the slot-major slab
    of windows `[S, layers, kernel - 1, C]`, `x` [S, C] the new inputs.
    Returns `(out [S, C] float32, conv)`; a dead slot's window stays
    bit-for-bit what it was."""
    window = jnp.concatenate([conv[:, page], x[:, None, :]], axis=1)
    w = w.astype(jnp.float32)
    if bias is not None:
        bias = bias.astype(jnp.float32)
    out = jnp.einsum("skc,kc->sc", window.astype(jnp.float32), w)
    if bias is not None:
        out = bias + out
    return out, conv.at[:, page].set(jnp.where(
        alive[:, None, None], window[:, 1:], conv[:, page]))


class Mamba2Mixer:
    """The Mamba-2 mixer (one group: B and C shared by all heads)."""

    kind = "mamba"
    ONES = ("m_norm", "D")

    def __init__(self, cfg, mesh, rms):
        self.cfg, self.mesh, self.rms = cfg, mesh, rms
        c = cfg
        self.state_shape = (c.mamba_n_heads, c.mamba_d_head, c.mamba_d_state)
        self.conv_shape = (c.mamba_d_conv - 1, c.conv_channels)

    def shapes(self, i):
        c = self.cfg
        d = c.hidden_size
        return {
            f"l{i}.m_in": (d, 2 * c.mamba_inner
                           + 2 * c.mamba_d_state + c.mamba_n_heads),
            f"l{i}.conv_w": (c.mamba_d_conv, c.conv_channels),
            f"l{i}.conv_b": (c.conv_channels,),
            f"l{i}.dt_bias": (c.mamba_n_heads,),
            f"l{i}.A_log": (c.mamba_n_heads,),
            f"l{i}.D": (c.mamba_n_heads,),
            f"l{i}.m_norm": (c.mamba_inner,),
            f"l{i}.m_out": (c.mamba_inner, d)}

    def kernel(self, slab_shape, dtype):
        from ..ops import pallas_ssm

        return _kernels_enabled(self.mesh) \
            and pallas_ssm.state_update_applies(slab_shape, dtype)

    # -- pieces -------------------------------------------------------------

    def _project(self, params, i, u):
        """`u` [T, D] -> z [T, inner], xBC [T, C], dt_raw [T, H]."""
        c = self.cfg
        with jax.named_scope("mamba.project"):
            return jnp.split(u @ params[f"l{i}.m_in"],
                             [c.mamba_inner, c.mamba_inner + c.conv_channels],
                             axis=-1)

    def _gates(self, params, i, dt_raw):
        """float32 step sizes `dt` [T, H] and log-decays `dt * A`."""
        with jax.named_scope("mamba.gates"):
            step = jax.nn.softplus(
                dt_raw.astype(jnp.float32)
                + params[f"l{i}.dt_bias"].astype(jnp.float32))
            return step, step * -jnp.exp(params[f"l{i}.A_log"]
                                         .astype(jnp.float32))

    def _out(self, params, i, y, x, z):
        """`y` [T, H, P] float32 (the recurrence's output) -> the mixer's
        output [T, D]: the skip `D x`, the gate, the gated RMSNorm, the
        output projection."""
        c = self.cfg
        t = y.shape[0]
        with jax.named_scope("mamba.out"):      # its norm nests: the
            # outermost scope names the work
            y = y + params[f"l{i}.D"].astype(jnp.float32)[None, :, None] \
                * x.astype(jnp.float32)
            y = y.reshape(t, c.mamba_inner) \
                * jax.nn.silu(z.astype(jnp.float32))
            return self.rms(y, params[f"l{i}.m_norm"]).astype(z.dtype) \
                @ params[f"l{i}.m_out"]

    def _split_xbc(self, xbc):
        c = self.cfg
        x, b, cc = jnp.split(xbc, [c.mamba_inner,
                                   c.mamba_inner + c.mamba_d_state], axis=-1)
        return (x.reshape(x.shape[0], c.mamba_n_heads, c.mamba_d_head), b, cc)

    # -- the sequence form ----------------------------------------------------

    def seq(self, params, i, u, length):
        """The mixer over one whole sequence `u` [L, D] of which the first
        `length` tokens are real. Returns `(out [L, D], state [H, P, N]
        float32, conv_tail [d_conv - 1, C])`: the recurrent state after
        token `length - 1` and the last `d_conv - 1` convolution inputs up
        to it (zeros before the sequence's start). Rows at and past
        `length` are steps of `dt = 0`: they neither move the state nor
        enter the tail, and their outputs are garbage nobody reads."""
        L, dt_ = u.shape[0], u.dtype
        z, xbc, dt_raw = self._project(params, i, u)
        with jax.named_scope("mamba.conv"):
            conv, tail = _conv_seq(xbc, params[f"l{i}.conv_w"],
                                   params[f"l{i}.conv_b"], length)
            x, b, cc = self._split_xbc(jax.nn.silu(conv).astype(dt_))
        step, log_a = self._gates(params, i, dt_raw)
        with jax.named_scope("mamba.gates"):
            real = (jnp.arange(L) < length)[:, None]
            step = jnp.where(real, step, 0.0)
            log_a = jnp.where(real, log_a, 0.0)
        with jax.named_scope("mamba.ssd"):
            y, state = self._ssd(x, b, cc, step, log_a)
        return self._out(params, i, y, x, z), state, tail

    def _ssd(self, x, b, c, step, log_a):
        """The recurrence over a whole sequence from a zero state, in chunks
        of `mamba_chunk_size`: x [L, H, P], b and c [L, N], step and log_a
        [L, H] float32. Returns `(y [L, H, P] float32, state [H, P, N]
        float32)`. A ragged last chunk is padded with steps of `dt = 0`.

        Inside a chunk, with `cs` the running sum of `log_a`: `y_t = sum_{s
        <= t} exp(cs_t - cs_s) dt_s (C_t . B_s) x_s + exp(cs_t) (S_in C_t)`
        and `S_out = exp(cs_end) S_in + sum_s exp(cs_end - cs_s) dt_s x_s
        (x) B_s`. What touches the carried state runs at matmul precision
        `highest`: the state is float32 and stays so."""
        q = self.cfg.mamba_chunk_size
        L, nh, hp = x.shape
        n = b.shape[1]
        pad = -L % q
        if pad:
            x, b, c, step, log_a = (jnp.pad(t, ((0, pad),) + ((0, 0),)
                                            * (t.ndim - 1))
                                    for t in (x, b, c, step, log_a))
        nc = (L + pad) // q
        f32 = jnp.float32
        chunks = (x.reshape(nc, q, nh, hp), b.reshape(nc, q, n),
                  c.reshape(nc, q, n), step.reshape(nc, q, nh),
                  log_a.reshape(nc, q, nh))
        causal = jnp.tril(jnp.ones((q, q), bool))[:, :, None]

        def chunk(state, xs):
            x_c, b_c, c_c, dt_c, la_c = xs
            cs = jnp.cumsum(la_c, axis=0)                        # [q, H]
            # exp(cs_t - cs_s) for s <= t; masked before the exp
            decay = jnp.exp(jnp.where(causal, cs[:, None, :] - cs[None, :, :],
                                      -jnp.inf))                 # [t, s, H]
            g = jnp.einsum("tn,sn->ts", c_c, b_c,
                           preferred_element_type=f32)
            m = g[:, :, None] * decay * dt_c[None, :, :]
            y = jnp.einsum("tsh,shp->thp", m, x_c.astype(f32))
            y = y + jnp.exp(cs)[:, :, None] * jnp.einsum(
                "tn,hpn->thp", c_c.astype(f32), state,
                precision=lax.Precision.HIGHEST)
            to_end = jnp.exp(cs[-1][None, :] - cs) * dt_c        # [s, H]
            state = jnp.exp(cs[-1])[:, None, None] * state + jnp.einsum(
                "shp,sn->hpn", to_end[:, :, None] * x_c.astype(f32),
                b_c.astype(f32), precision=lax.Precision.HIGHEST)
            return state, y

        state, y = lax.scan(chunk, jnp.zeros((nh, hp, n), f32), chunks)
        return y.reshape(nc * q, nh, hp)[:L], state

    # -- one step -------------------------------------------------------------

    def step(self, params, i, u, ssm, conv, page, alive):
        """One token for every slot through Mamba layer `i`: `u` [S, D],
        the slot-major state slabs, `alive` [S]. The state update happens on
        the layer's page of the slab where it lies; a dead slot's state and
        convolution window stay bit-for-bit what they were."""
        z, xbc, dt_raw = self._project(params, i, u)
        with jax.named_scope("mamba.conv"):
            out, conv = _conv_step(conv, page, xbc, params[f"l{i}.conv_w"],
                                   params[f"l{i}.conv_b"], alive)
            x, b, cc = self._split_xbc(jax.nn.silu(out).astype(u.dtype))
        step, log_a = self._gates(params, i, dt_raw)
        with jax.named_scope("mamba.state_update"):
            f32 = jnp.float32
            decay, dtx = jnp.exp(log_a), step[:, :, None] * x.astype(f32)
            kernel = self.kernel(ssm.shape, ssm.dtype)
            if telemetry._enabled:
                telemetry.counter("mamba.state_update."
                                  + ("kernel" if kernel else "xla")).inc()
            if kernel:
                from ..ops import pallas_attention as pa
                from ..ops import pallas_ssm

                y, ssm = pallas_ssm.state_update(
                    ssm, jnp.int32(page), decay, dtx, b, cc, alive,
                    interpret=pa.pallas_interpret())
            else:
                old = ssm[:, page]                              # [S,H,P,N]
                new = decay[:, :, None, None] * old + (
                    dtx[:, :, :, None] * b.astype(f32)[:, None, None, :])
                y = jnp.sum(new * cc.astype(f32)[:, None, None, :], axis=-1)
                ssm = ssm.at[:, page].set(
                    jnp.where(alive[:, None, None, None], new, old))
        return self._out(params, i, y, x, z), ssm, conv


class GatedDeltaMixer:
    """The gated delta rule (module docstring). Weights of layer `i`:

        g_in    [D, 2 H dk + 2 H dv + 2 H]  q | k | v | gate | b | a, fused
        conv_w  [kernel, 2 H dk + H dv]     the three streams' depthwise
                                            convolutions side by side, no bias
        dt_bias, A_log  [H]                 the decay: a = exp(-exp(A_log)
                                            softplus(x W_a + dt_bias))
        g_norm  [dv]                        RMSNorm of a head's output
        g_out   [H dv, D]

    Scopes: `gdn.project`, `gdn.conv`, `gdn.gates`, `gdn.chunk` (prefill) |
    `gdn.state_update` (decode), `gdn.out`."""

    kind = "linear_attention"
    ONES = ("g_norm",)
    L2_EPS = 1e-6           # under the root of q's and k's norm a head

    def __init__(self, cfg, mesh, rms):
        self.cfg, self.mesh, self.rms = cfg, mesh, rms
        c = cfg
        self.heads, self.dk, self.dv = (c.linear_num_heads,
                                        c.linear_key_head_dim,
                                        c.linear_value_head_dim)
        self.channels = self.heads * (2 * self.dk + self.dv)
        self.state_shape = (self.dk, self.heads * self.dv)
        self.conv_shape = (c.linear_conv_kernel_dim - 1, self.channels)

    def shapes(self, i):
        d, h, dv = self.cfg.hidden_size, self.heads, self.dv
        return {
            f"l{i}.g_in": (d, self.channels + h * dv + 2 * h),
            f"l{i}.conv_w": (self.cfg.linear_conv_kernel_dim, self.channels),
            f"l{i}.dt_bias": (h,), f"l{i}.A_log": (h,),
            f"l{i}.g_norm": (dv,), f"l{i}.g_out": (h * dv, d)}

    def kernel(self, slab_shape, dtype):
        from ..ops import pallas_ssm

        return _kernels_enabled(self.mesh) \
            and pallas_ssm.gdn_update_applies(slab_shape, dtype, self.heads)

    # -- pieces -------------------------------------------------------------

    def _project(self, params, i, u):
        """`u` [T, D] -> qkv [T, C] (the convolution's input), gate [T, H
        dv], b_raw and a_raw [T, H]."""
        h = self.heads
        with jax.named_scope("gdn.project"):
            at = np.cumsum([self.channels, h * self.dv, h])
            return jnp.split(u @ params[f"l{i}.g_in"], at, axis=-1)

    def _heads(self, qkv):
        """The convolved streams `[T, C]` -> q, k [T, H, dk], v [T, H, dv]."""
        h, dk = self.heads, self.dk
        t = qkv.shape[0]
        q, k, v = jnp.split(qkv, [h * dk, 2 * h * dk], axis=-1)
        return (q.reshape(t, h, dk), k.reshape(t, h, dk),
                v.reshape(t, h, self.dv))

    def _gates(self, params, i, q, k, b_raw, a_raw):
        """float32: `q / |q| * dk^-1/2` and `k / |k|` a head, the write
        strength `beta` [T, H] in (0, 2) (in (0, 1) without
        `linear_allow_neg_eigval`) and the log-decay `g <= 0` [T, H]."""
        f32 = jnp.float32
        with jax.named_scope("gdn.gates"):
            q, k = q.astype(f32), k.astype(f32)
            q = q * lax.rsqrt((q * q).sum(-1, keepdims=True) + self.L2_EPS) \
                * self.dk ** -0.5
            k = k * lax.rsqrt((k * k).sum(-1, keepdims=True) + self.L2_EPS)
            beta = jax.nn.sigmoid(b_raw.astype(f32))
            if self.cfg.linear_allow_neg_eigval:
                beta = 2.0 * beta
            g = -jnp.exp(params[f"l{i}.A_log"].astype(f32)) * jax.nn.softplus(
                a_raw.astype(f32) + params[f"l{i}.dt_bias"].astype(f32))
            return q, k, beta, g

    def _out(self, params, i, o, gate):
        """`o` [T, H, dv] float32 (the recurrence's output) -> the mixer's
        output [T, D]: RMSNorm a head, the SiLU gate, the output
        projection."""
        with jax.named_scope("gdn.out"):
            o = self.rms(o, params[f"l{i}.g_norm"]).reshape(o.shape[0], -1) \
                * jax.nn.silu(gate.astype(jnp.float32))
            return o.astype(gate.dtype) @ params[f"l{i}.g_out"]

    # -- the sequence form ----------------------------------------------------

    def seq(self, params, i, u, length):
        """The mixer over one whole sequence `u` [L, D] of which the first
        `length` tokens are real. Returns `(out [L, D], state [dk, H dv]
        float32, conv_tail [kernel - 1, C])`, as `Mamba2Mixer.seq`. Rows at
        and past `length` are steps of `g = 0, beta = 0`: they neither move
        the state nor enter the tail."""
        L = u.shape[0]
        qkv, gate, b_raw, a_raw = self._project(params, i, u)
        with jax.named_scope("gdn.conv"):
            conv, tail = _conv_seq(qkv, params[f"l{i}.conv_w"], None, length)
            q, k, v = self._heads(jax.nn.silu(conv).astype(u.dtype))
        q, k, beta, g = self._gates(params, i, q, k, b_raw, a_raw)
        with jax.named_scope("gdn.gates"):
            real = (jnp.arange(L) < length)[:, None]
            beta = jnp.where(real, beta, 0.0)
            g = jnp.where(real, g, 0.0)
        with jax.named_scope("gdn.chunk"):
            o, state = self.chunked(q, k, v.astype(jnp.float32), beta, g)
            state = state.transpose(1, 0, 2).reshape(self.state_shape)
        return self._out(params, i, o, gate), state, tail

    def chunked(self, q, k, v, beta, g):
        """The delta rule over a whole sequence from a zero state, in chunks
        of `gdn_chunk_size`: q, k [L, H, dk], v [L, H, dv], beta and g [L, H],
        all float32. Returns `(o [L, H, dv], state [H, dk, dv])`. A ragged
        last chunk is padded with steps of `g = 0, beta = 0`.

        Inside a chunk (the WY form), with `cs` the running sum of `g`, `A_ts
        = beta_t (k_t . k_s) exp(cs_t - cs_s)` for `s < t`, and `T = (I +
        A)^-1` (a unit-lower-triangular solve): `U = T (beta v)`, `W = T
        (beta exp(cs) k)`; the tokens' corrected values are `V' = U - W
        S_in`, and

            o_t   = exp(cs_t) S_in^T q_t + sum_{s <= t} exp(cs_t - cs_s)
                    (q_t . k_s) V'_s
            S_out = exp(cs_end) S_in + sum_s exp(cs_end - cs_s) k_s (x) V'_s

        The solve runs for every chunk at once, before the scan that
        carries the state; what touches the carried state runs at matmul
        precision `highest`."""
        c = self.cfg.gdn_chunk_size
        L, nh, dk = q.shape
        dv = v.shape[2]
        pad = -L % c
        if pad:
            q, k, v, beta, g = (jnp.pad(t, ((0, pad),) + ((0, 0),)
                                        * (t.ndim - 1))
                                for t in (q, k, v, beta, g))
        nc = (L + pad) // c
        hi = lax.Precision.HIGHEST
        # [chunks, H, c, ..]: a head's chunk is a matrix
        q, k, v = (t.reshape(nc, c, nh, -1).transpose(0, 2, 1, 3)
                   for t in (q, k, v))
        beta, g = (t.reshape(nc, c, nh).transpose(0, 2, 1)
                   for t in (beta, g))
        cs = jnp.cumsum(g, axis=-1)                             # [nc, H, c]
        lower = jnp.tril(jnp.ones((c, c), bool))
        # exp(cs_t - cs_s) for s <= t; masked before the exp
        decay = jnp.exp(jnp.where(lower, cs[..., :, None] - cs[..., None, :],
                                  -jnp.inf))                    # [.., t, s]
        kb = k * beta[..., None]
        a = jnp.where(jnp.tril(jnp.ones((c, c), bool), -1),
                      jnp.einsum("nhtd,nhsd->nhts", kb, k) * decay, 0.0)
        uw = jax.scipy.linalg.solve_triangular(
            a + jnp.eye(c, dtype=a.dtype),
            jnp.concatenate([v * beta[..., None],
                             kb * jnp.exp(cs)[..., None]], axis=-1),
            lower=True, unit_diagonal=True)
        u, w = uw[..., :dv], uw[..., dv:]
        inside = jnp.einsum("nhtd,nhsd->nhts", q, k) * decay    # s <= t
        q_in = q * jnp.exp(cs)[..., None]
        k_out = k * jnp.exp(cs[..., -1:] - cs)[..., None]
        last = jnp.exp(cs[..., -1])                             # [nc, H]

        def chunk(state, xs):
            u_c, w_c, in_c, q_c, k_c, last_c = xs
            v_new = u_c - jnp.einsum("htk,hkv->htv", w_c, state,
                                     precision=hi)
            o = jnp.einsum("htk,hkv->htv", q_c, state, precision=hi) \
                + jnp.einsum("hts,hsv->htv", in_c, v_new)
            state = last_c[:, None, None] * state + jnp.einsum(
                "hsk,hsv->hkv", k_c, v_new, precision=hi)
            return state, o

        state, o = lax.scan(chunk, jnp.zeros((nh, dk, dv), jnp.float32),
                            (u, w, inside, q_in, k_out, last))
        return o.transpose(0, 2, 1, 3).reshape(nc * c, nh, dv)[:L], state

    # -- one step -------------------------------------------------------------

    def step(self, params, i, u, state, conv, page, alive):
        """One token for every slot through layer `i`: `u` [S, D], the
        slot-major slabs (`state` [S, layers, dk, H dv] float32), `alive`
        [S]. A dead slot's state and convolution window stay bit-for-bit
        what they were."""
        qkv, gate, b_raw, a_raw = self._project(params, i, u)
        with jax.named_scope("gdn.conv"):
            out, conv = _conv_step(conv, page, qkv, params[f"l{i}.conv_w"],
                                   None, alive)
            q, k, v = self._heads(jax.nn.silu(out).astype(u.dtype))
        q, k, beta, g = self._gates(params, i, q, k, b_raw, a_raw)
        with jax.named_scope("gdn.state_update"):
            v, alpha = v.astype(jnp.float32), jnp.exp(g)
            kernel = self.kernel(state.shape, state.dtype)
            if telemetry._enabled:
                telemetry.counter("gdn.state_update."
                                  + ("kernel" if kernel else "xla")).inc()
            if kernel:
                from ..ops import pallas_attention as pa
                from ..ops import pallas_ssm

                o, state = pallas_ssm.gdn_state_update(
                    state, jnp.int32(page), alpha, beta, q, k, v, alive,
                    interpret=pa.pallas_interpret())
            else:
                o, new = gdn_step_xla(state[:, page], alpha, beta, q, k, v)
                state = state.at[:, page].set(
                    jnp.where(alive[:, None, None], new, state[:, page]))
        return self._out(params, i, o, gate), state, conv


class ShortConvMixer:
    """The gated short convolution (module docstring). Weights of layer `i`:

        c_in    [D, 3 D]        B | C | x, fused in the published order
        conv_w  [kernel, D]     one tap vector a channel, no bias
        c_out   [D, D]

    The product `B * x` is taken in the served dtype (it is what the window
    keeps), the taps' sum and the gate `C *` in float32. Scopes:
    `shortconv.project`, `shortconv.conv`, `shortconv.out`."""

    kind = "conv"
    ONES = ()
    state_shape = None      # no recurrent state: the window is all it keeps

    def __init__(self, cfg, mesh, rms):
        del mesh, rms
        self.cfg = cfg
        self.conv_shape = (cfg.conv_L_cache - 1, cfg.hidden_size)

    def shapes(self, i):
        d = self.cfg.hidden_size
        return {f"l{i}.c_in": (d, 3 * d),
                f"l{i}.conv_w": (self.cfg.conv_L_cache, d),
                f"l{i}.c_out": (d, d)}

    def kernel(self, slab_shape, dtype):
        return False

    def _project(self, params, i, u):
        """`u` [T, D] -> the gate `C` [T, D] and the convolution's input `B
        * x` [T, D]."""
        with jax.named_scope("shortconv.project"):
            b, c, x = jnp.split(u @ params[f"l{i}.c_in"], 3, axis=-1)
            return c, b * x

    def _out(self, params, i, c, conv):
        """`(C * conv) W_out` for the taps' sums `conv` [T, D] float32."""
        with jax.named_scope("shortconv.out"):
            y = c.astype(jnp.float32) * conv
            return y.astype(c.dtype) @ params[f"l{i}.c_out"]

    def seq(self, params, i, u, length):
        """The mixer over one whole sequence `u` [L, D] of which the first
        `length` tokens are real: `(out [L, D], None, window [kernel - 1,
        D])` — the window holds the last inputs up to token `length - 1`
        (zeros before the sequence's start), so a bucket's padding never
        enters it."""
        c, bx = self._project(params, i, u)
        with jax.named_scope("shortconv.conv"):
            conv, tail = _conv_seq(bx, params[f"l{i}.conv_w"], None, length)
        return self._out(params, i, c, conv), None, tail

    def step(self, params, i, u, state, conv, page, alive):
        """One token for every slot through layer `i`: `u` [S, D], the
        slot-major slab of windows. A dead slot's window stays bit-for-bit
        what it was."""
        c, bx = self._project(params, i, u)
        with jax.named_scope("shortconv.conv"):
            out, conv = _conv_step(conv, page, bx, params[f"l{i}.conv_w"],
                                   None, alive)
        return self._out(params, i, c, out), state, conv


def gdn_step_xla(old, alpha, beta, q, k, v):
    """One step of the gated delta rule in XLA, the mathematics of
    `pallas_ssm.gdn_state_update`: `old` [S, dk, H dv] float32, `alpha` and
    `beta` [S, H], `q` and `k` [S, H, dk], `v` [S, H, dv], all float32.
    Returns `(o [S, H, dv], new [S, dk, H dv])`; reads the page three
    times."""
    s, dk, _ = old.shape
    h, dv = v.shape[1:]
    hi = lax.Precision.HIGHEST
    kept = alpha[:, None, :, None] * old.reshape(s, dk, h, dv)
    u = v - jnp.einsum("skhv,shk->shv", kept, k, precision=hi)
    new = kept + (beta[:, :, None] * k).transpose(0, 2, 1)[..., None] \
        * u[:, None]
    o = jnp.einsum("skhv,shk->shv", new, q, precision=hi)
    return o, new.reshape(old.shape)


MIXERS = {m.kind: m
          for m in (Mamba2Mixer, GatedDeltaMixer, ShortConvMixer)}

"""S=4096 MFU ceiling analysis (VERDICT r4 #3) — run on TPU.

Timing rule learned the hard way (see git history of this file): chains
must feed each iteration's OUTPUT tensor back into the next iteration's
INPUT. A scalar carry multiplied onto a matmul operand gets commuted by
XLA's algebraic simplifier (c*(A@B)) and the matmul hoists out of the
scan — yielding impossible >100%-of-peak readings."""
import sys, os
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import time, numpy as np, jax, jax.numpy as jnp
from jax import lax

def sync(v): return float(np.asarray(jax.device_get(v)))
PEAK = 197e12
B, H, S, D = 2, 12, 4096, 64   # bench_gpt longctx attention shape

LAT = [0.0]
def timed(f, *a, reps=1):
    sync(f(*a)); ts=[]
    for _ in range(3):
        t0=time.perf_counter(); sync(f(*a)); ts.append((time.perf_counter()-t0)/reps)
    return sorted(ts)[1] - LAT[0] / reps

def calibrate():
    tiny = jax.jit(lambda a: jnp.sum(a))
    x = jnp.ones((8, 8))
    sync(tiny(x)); ls = []
    for _ in range(5):
        t0 = time.perf_counter(); sync(tiny(x)); ls.append(time.perf_counter() - t0)
    LAT[0] = sorted(ls)[2]
    print(f"dispatch latency: {LAT[0]*1e3:.1f} ms (subtracted /reps)")
calibrate()

rng = np.random.RandomState(0)
# paddle layout (B, S, H, D) — flash_attention_fwd's contract
q = jnp.asarray(rng.randn(B, S, H, D).astype('f4')*0.1, jnp.bfloat16)
k = jnp.asarray(rng.randn(B, S, H, D).astype('f4')*0.1, jnp.bfloat16)
v = jnp.asarray(rng.randn(B, S, H, D).astype('f4')*0.1, jnp.bfloat16)
fl_attn = 2 * 2 * B * H * S * S * D * 0.5          # causal fwd flops

from paddle_tpu.ops.pallas.flash_attention import flash_attention_fwd
from paddle_tpu.nn.functional.attention import (_attention_core,
                                                _select_flash)

# --- 1. flash fwd chain (output feeds next q)
RF = 500
@jax.jit
def fwd_chain(q, k, v):
    def rep(qc, _):
        o = flash_attention_fwd(qc, k, v, causal=True)
        return (q + o * jnp.bfloat16(1e-3)).astype(jnp.bfloat16), None
    qf, _ = lax.scan(rep, q, None, length=RF)
    return jnp.sum(qf.astype(jnp.float32))
t_fwd = timed(fwd_chain, q, k, v, reps=RF)
print(f"flash fwd : {t_fwd*1e3:.3f} ms  {fl_attn/t_fwd/1e12:.1f} TF/s = {fl_attn/t_fwd/PEAK*100:.0f}% peak")

# --- 2. flash fwd+bwd chain (grad feeds next q)
RB = 200
@jax.jit
def fb_chain(q, k, v):
    def loss(qq, kk, vv):
        sel = _select_flash(qq.shape[1], kk.shape[1], qq.shape[3],
                            True, has_mask=False, mask_is_keybias=False,
                            scale=None, heads=(qq.shape[2], kk.shape[2]))
        return jnp.sum(_attention_core(qq, kk, vv, True, None, sel)
                       .astype(jnp.float32))
    g = jax.grad(loss, argnums=(0,))
    def rep(qc, _):
        gq, = g(qc, k, v)
        return (q + gq.astype(jnp.bfloat16) * jnp.bfloat16(1e-3)), None
    qf, _ = lax.scan(rep, q, None, length=RB)
    return jnp.sum(qf.astype(jnp.float32))
t_fb = timed(fb_chain, q, k, v, reps=RB)
fl_fb = fl_attn * 3.5
print(f"flash f+b : {t_fb*1e3:.3f} ms  {fl_fb/t_fb/1e12:.1f} TF/s = {fl_fb/t_fb/PEAK*100:.0f}% peak")

# --- 3. dense attention fwd same shape
RD = 60
@jax.jit
def dense_chain(q, k, v):
    mask = jnp.tril(jnp.ones((S, S), jnp.bool_))
    def rep(qc, _):
        s = jnp.einsum("bqhd,bkhd->bhqk", qc, k) / np.sqrt(D)
        s = jnp.where(mask, s.astype(jnp.float32), -1e30)
        p = jax.nn.softmax(s, axis=-1).astype(jnp.bfloat16)
        o = jnp.einsum("bhqk,bkhd->bqhd", p, v)
        return (q + o * jnp.bfloat16(1e-3)), None
    qf, _ = lax.scan(rep, q, None, length=RD)
    return jnp.sum(qf.astype(jnp.float32))
try:
    t_dense = timed(dense_chain, q, k, v, reps=RD)
    print(f"dense fwd : {t_dense*1e3:.3f} ms  ({t_dense/t_fwd:.2f}x flash fwd)")
except Exception as e:
    print("dense fwd : FAIL", repr(e)[:80])

# --- 4. non-attention remainder: proj+MLP block at B*S=8192 tokens
HID = 768
RM = 500
x = jnp.asarray(rng.randn(B * S, HID).astype('f4') * 0.1, jnp.bfloat16)
Wqkv = jnp.asarray(rng.randn(HID, 3 * HID).astype('f4') * 0.02, jnp.bfloat16)
Wo = jnp.asarray(rng.randn(HID, HID).astype('f4') * 0.02, jnp.bfloat16)
W1 = jnp.asarray(rng.randn(HID, 4 * HID).astype('f4') * 0.02, jnp.bfloat16)
W2 = jnp.asarray(rng.randn(4 * HID, HID).astype('f4') * 0.02, jnp.bfloat16)
@jax.jit
def mm_chain(x, Wqkv, Wo, W1, W2):
    def rep(xc, _):
        h = xc @ Wqkv
        h2 = (h[:, :HID]) @ Wo
        h3 = jax.nn.gelu(h2 @ W1)
        h4 = h3 @ W2
        return (x + h4 * jnp.bfloat16(1e-3)).astype(jnp.bfloat16), None
    xf, _ = lax.scan(rep, x, None, length=RM)
    return jnp.sum(xf.astype(jnp.float32))
t_mm = timed(mm_chain, x, Wqkv, Wo, W1, W2, reps=RM)
# NOTE: XLA DCEs the unused 2/3 of the qkv projection (only
# h[:, :HID] is consumed), so count HID not 3*HID for that matmul
fl_mm = 2 * B * S * HID * (HID + HID + 4*HID + 4*HID)
print(f"proj+mlp  : {t_mm*1e3:.3f} ms  {fl_mm/t_mm/1e12:.1f} TF/s = {fl_mm/t_mm/PEAK*100:.0f}% peak")

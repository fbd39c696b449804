"""Per-kernel validation: Pallas (interpret=True) and the XLA paths swept
over shapes/dtypes against the pure-jnp oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.flash_attention.ref import attention_reference
from repro.kernels.flash_attention.xla import attention_xla
from repro.kernels.decode_attention.kernel import decode_attention_pallas
from repro.kernels.decode_attention.ref import decode_attention_reference
from repro.kernels.mamba2_scan.kernel import ssd_scan_pallas
from repro.kernels.mamba2_scan.ref import ssd_chunked, ssd_sequential
from repro.kernels.rwkv6_scan.kernel import wkv6_scan_pallas
from repro.kernels.rwkv6_scan.ref import wkv6_chunked, wkv6_sequential
from repro.kernels.fleet_mlp.kernel import fleet_mlp_pallas
from repro.kernels.fleet_mlp.ref import fleet_mlp_reference

TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


def _mk(rng, shape, dtype):
    return jnp.asarray(rng.normal(size=shape), jnp.float32).astype(dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,H,KV,D,bq,bk", [
    (1, 128, 4, 4, 32, 64, 64),       # MHA
    (2, 256, 4, 2, 32, 128, 64),      # GQA 2:1
    (1, 128, 8, 2, 64, 64, 128),      # GQA 4:1, wide head
])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention(rng, dtype, B, S, H, KV, D, bq, bk, causal):
    q, k, v = (_mk(rng, (B, S, n, D), dtype) for n in (H, KV, KV))
    got = flash_attention_pallas(q, k, v, causal=causal, block_q=bq,
                                 block_k=bk, interpret=True)
    want = attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


@pytest.mark.parametrize("causal", [True, False])
def test_attention_xla_matches_ref(rng, causal):
    q, k, v = (_mk(rng, (2, 256, 4, 32), jnp.float32) for _ in range(3))
    k = k[:, :, :2]
    v = v[:, :, :2]
    got = attention_xla(q, k, v, causal=causal, q_chunk=64)
    want = attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_attention_cross_q_kv_lengths(rng):
    """Chunked prefill continuation: Sq < Skv with aligned ends."""
    q = _mk(rng, (1, 64, 4, 32), jnp.float32)
    k = _mk(rng, (1, 256, 4, 32), jnp.float32)
    v = _mk(rng, (1, 256, 4, 32), jnp.float32)
    got = attention_xla(q, k, v, causal=True, q_chunk=32)
    want = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,H,KV,D,bk", [
    (3, 256, 4, 2, 32, 64),
    (2, 128, 8, 8, 64, 128),
])
def test_decode_attention(rng, dtype, B, S, H, KV, D, bk):
    q = _mk(rng, (B, H, D), dtype)
    kc = _mk(rng, (B, S, KV, D), dtype)
    vc = _mk(rng, (B, S, KV, D), dtype)
    lens = jnp.asarray(rng.integers(1, S + 1, B), jnp.int32)
    got = decode_attention_pallas(q, kc, vc, lens, block_k=bk, interpret=True)
    want = decode_attention_reference(q, kc, vc, lens)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


@pytest.mark.parametrize("B,S,H,P,N,chunk", [
    (2, 128, 3, 16, 16, 32),
    (1, 64, 2, 8, 32, 16),
    (1, 96, 1, 32, 16, 32),
])
def test_mamba2_kernel_vs_sequential(rng, B, S, H, P, N, chunk):
    x = _mk(rng, (B, S, H, P), jnp.float32)
    dt = jnp.asarray(rng.uniform(1e-3, 0.1, (B, S, H)), jnp.float32)
    A = jnp.asarray(-rng.uniform(0.5, 2.0, (H,)), jnp.float32)
    Bm = _mk(rng, (B, S, 1, N), jnp.float32)
    Cm = _mk(rng, (B, S, 1, N), jnp.float32)
    D = _mk(rng, (H,), jnp.float32)
    got_y, got_s = ssd_scan_pallas(x, dt, A, Bm, Cm, D, chunk=chunk,
                                   interpret=True)
    want_y, want_s = ssd_sequential(x, dt, A, Bm, Cm, D)
    np.testing.assert_allclose(got_y, want_y, atol=3e-5, rtol=3e-5)
    np.testing.assert_allclose(got_s, want_s, atol=3e-5, rtol=3e-5)


def test_mamba2_chunked_xla_init_state(rng):
    """XLA chunked path: continuation with init_state == longer sequential."""
    B, S, H, P, N = 1, 128, 2, 8, 8
    x = _mk(rng, (B, S, H, P), jnp.float32)
    dt = jnp.asarray(rng.uniform(1e-3, 0.1, (B, S, H)), jnp.float32)
    A = jnp.asarray(-rng.uniform(0.5, 2.0, (H,)), jnp.float32)
    Bm = _mk(rng, (B, S, 1, N), jnp.float32)
    Cm = _mk(rng, (B, S, 1, N), jnp.float32)
    D = _mk(rng, (H,), jnp.float32)
    y_full, s_full = ssd_sequential(x, dt, A, Bm, Cm, D)
    half = S // 2
    _, s1 = ssd_chunked(x[:, :half], dt[:, :half], A, Bm[:, :half],
                        Cm[:, :half], D, chunk=32)
    y2, s2 = ssd_chunked(x[:, half:], dt[:, half:], A, Bm[:, half:],
                         Cm[:, half:], D, init_state=s1, chunk=32)
    np.testing.assert_allclose(y2, y_full[:, half:], atol=3e-5, rtol=3e-5)
    np.testing.assert_allclose(s2, s_full, atol=3e-5, rtol=3e-5)


@pytest.mark.parametrize("wmin", [0.4, 0.001])   # mild + aggressive decay
@pytest.mark.parametrize("B,S,H,K,chunk", [
    (2, 128, 3, 16, 32),
    (1, 64, 2, 32, 16),
])
def test_rwkv6_kernel_vs_sequential(rng, wmin, B, S, H, K, chunk):
    r = _mk(rng, (B, S, H, K), jnp.float32)
    k = _mk(rng, (B, S, H, K), jnp.float32)
    v = _mk(rng, (B, S, H, K), jnp.float32)
    w = jnp.asarray(rng.uniform(wmin, 0.999, (B, S, H, K)), jnp.float32)
    u = _mk(rng, (H, K), jnp.float32)
    got_y, got_s = wkv6_scan_pallas(r, k, v, w, u, chunk=chunk, interpret=True)
    want_y, want_s = wkv6_sequential(r, k, v, w, u)
    np.testing.assert_allclose(got_y, want_y, atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(got_s, want_s, atol=2e-4, rtol=2e-4)


def test_rwkv6_chunked_xla_moderate_decay(rng):
    B, S, H, K = 2, 96, 2, 16
    r = _mk(rng, (B, S, H, K), jnp.float32)
    k = _mk(rng, (B, S, H, K), jnp.float32)
    v = _mk(rng, (B, S, H, K), jnp.float32)
    w = jnp.asarray(rng.uniform(0.37, 0.999, (B, S, H, K)), jnp.float32)
    u = _mk(rng, (H, K), jnp.float32)
    got_y, got_s = wkv6_chunked(r, k, v, w, u, chunk=32)
    want_y, want_s = wkv6_sequential(r, k, v, w, u)
    np.testing.assert_allclose(got_y, want_y, atol=3e-4, rtol=3e-4)
    np.testing.assert_allclose(got_s, want_s, atol=3e-4, rtol=3e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("N,b,F,Hd,depth,block_n", [
    (16, 4, 8, 32, 3, 4),
    (8, 1, 54, 64, 5, 8),      # ANN shape (4 hidden + out)
    (4, 2, 16, 16, 1, 2),      # single layer
    (4, 1, 54, 512, 5, None),  # paper width, block_n from kernel.vmem_plan
])
def test_fleet_mlp(rng, dtype, N, b, F, Hd, depth, block_n):
    x = _mk(rng, (N, b, F), dtype)
    sizes = [F] + [Hd] * (depth - 1) + [1]
    ws = [_mk(rng, (N, sizes[i], sizes[i + 1]), dtype) for i in range(depth)]
    bs = [_mk(rng, (N, sizes[i + 1]), dtype) for i in range(depth)]
    got = fleet_mlp_pallas(x, ws, bs, block_n=block_n, interpret=True)
    want = fleet_mlp_reference(x, ws, bs)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=TOL[dtype] * 10, rtol=TOL[dtype] * 10)


@pytest.mark.parametrize("N,width,limit_raised", [
    (1024, 64, False), (24, 64, False), (256, 512, False), (7, 512, False),
    (16, 1024, True),
])
def test_fleet_mlp_vmem_plan(N, width, limit_raised):
    """block_n divides N (no padded instances) and its double-buffered
    blocks fit the default scoped VMEM; only an instance too large for it
    raises the kernel's VMEM limit."""
    from repro.kernels.fleet_mlp.kernel import _SCOPED_VMEM_BYTES, vmem_plan
    widths = [width] * 4 + [1]
    block_n, limit = vmem_plan(N, 1, 54, widths, jnp.float32)
    assert N % block_n == 0
    assert (limit is not None) == limit_raised
    if limit is not None:
        assert block_n == 1 and limit > _SCOPED_VMEM_BYTES

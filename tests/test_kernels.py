"""Per-kernel allclose sweeps against the pure-jnp oracles (interpret mode).

Each Pallas kernel is swept over shapes/dtypes and asserted against
repro.kernels.ref; the SSD *chunked* model path is additionally asserted
against the sequential-recurrence reference.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

jax.config.update("jax_enable_x64", False)


def tol(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 \
        else dict(atol=2e-5, rtol=2e-5)


def _assert_close(got, want, dtype):
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **tol(dtype))


# --------------------------------------------------------------------- #
# flash attention
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,H,Hkv,D", [
    (1, 64, 4, 4, 32),      # MHA
    (2, 128, 4, 2, 32),     # GQA
    (1, 96, 8, 1, 16),      # MQA, ragged seq (padding path)
    (2, 256, 2, 2, 64),
])
def test_flash_attention_causal(B, S, H, Hkv, D, dtype):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(k1, (B, S, H, D), dtype)
    k = jax.random.normal(k2, (B, S, Hkv, D), dtype)
    v = jax.random.normal(k3, (B, S, Hkv, D), dtype)
    got = ops.flash_attention(q, k, v, causal=True, block_q=32, block_kv=32)
    want = ref.flash_attention_ref(q, k, v, causal=True)
    _assert_close(got, want, dtype)


@pytest.mark.parametrize("window", [16, 48, 100])
def test_flash_attention_window(window):
    B, S, H, Hkv, D = 2, 128, 4, 1, 32
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(k1, (B, S, H, D))
    k = jax.random.normal(k2, (B, S, Hkv, D))
    v = jax.random.normal(k3, (B, S, Hkv, D))
    got = ops.flash_attention(q, k, v, causal=True, window=window,
                              block_q=32, block_kv=32)
    want = ref.flash_attention_ref(q, k, v, causal=True, window=window)
    _assert_close(got, want, jnp.float32)


def test_flash_attention_matches_model_blocked_path():
    """The model's jnp blocked attention and the kernel agree."""
    from repro.models.common import blocked_attention
    B, S, H, Hkv, D = 1, 128, 4, 2, 32
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(k1, (B, S, H, D))
    k = jax.random.normal(k2, (B, S, Hkv, D))
    v = jax.random.normal(k3, (B, S, Hkv, D))
    got = ops.flash_attention(q, k, v, causal=True, block_q=32, block_kv=32)
    want = blocked_attention(q, k, v, causal=True, block_q=32, block_kv=32)
    _assert_close(got, want, jnp.float32)


# --------------------------------------------------------------------- #
# decode attention
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,H,Hkv,D", [
    (2, 128, 4, 2, 32),
    (1, 256, 8, 8, 64),
    (3, 96, 4, 1, 16),      # ragged cache length (padding path)
])
def test_decode_attention(B, S, H, Hkv, D, dtype):
    keys = jax.random.split(jax.random.PRNGKey(3), 4)
    q = jax.random.normal(keys[0], (B, 1, H, D), dtype)
    kc = jax.random.normal(keys[1], (B, Hkv, S, D), dtype)
    vc = jax.random.normal(keys[2], (B, Hkv, S, D), dtype)
    lengths = jax.random.randint(keys[3], (B,), 1, S + 1)
    got = ops.decode_attention(q, kc, vc, lengths, block_kv=32)
    want = ref.decode_attention_ref(q, kc, vc, lengths)
    _assert_close(got, want, dtype)


def test_decode_attention_matches_model_decode():
    """Model decode_attention (full cache) == kernel at length = pos+1."""
    from repro.models.common import decode_attention as model_decode
    B, S, H, Hkv, D = 2, 64, 4, 2, 32
    keys = jax.random.split(jax.random.PRNGKey(4), 3)
    q = jax.random.normal(keys[0], (B, 1, H, D))
    kc = jax.random.normal(keys[1], (B, Hkv, S, D))
    vc = jax.random.normal(keys[2], (B, Hkv, S, D))
    pos = 37
    got = ops.decode_attention(q, kc, vc, jnp.full((B,), pos + 1), block_kv=32)
    want = model_decode(q, kc, vc, pos)
    _assert_close(got, want, jnp.float32)


# --------------------------------------------------------------------- #
# SSD scan
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,H,P,G,N,chunk", [
    (1, 64, 2, 8, 1, 16, 16),
    (2, 128, 4, 16, 1, 32, 32),
    (1, 64, 4, 8, 2, 16, 16),    # grouped B/C
])
def test_ssd_scan(B, S, H, P, G, N, chunk, dtype):
    keys = jax.random.split(jax.random.PRNGKey(5), 5)
    x = jax.random.normal(keys[0], (B, S, H, P), dtype)
    dt = jax.nn.softplus(jax.random.normal(keys[1], (B, S, H))).astype(jnp.float32)
    a_log = jnp.log(jnp.linspace(1.0, 4.0, H))
    B_in = jax.random.normal(keys[2], (B, S, G, N), dtype)
    C_in = jax.random.normal(keys[3], (B, S, G, N), dtype)
    got = ops.ssd_scan(x, dt, a_log, B_in, C_in, chunk=chunk)
    want, _ = ref.ssd_scan_ref(x, dt, a_log, B_in, C_in)
    _assert_close(got, want, dtype)


def test_ssd_chunked_model_path_matches_sequential():
    """The model's chunked SSD == sequential recurrence, incl. final state."""
    B, S, H, P, G, N = 2, 96, 3, 8, 1, 16
    keys = jax.random.split(jax.random.PRNGKey(6), 4)
    x = jax.random.normal(keys[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(keys[1], (B, S, H)))
    a_log = jnp.log(jnp.linspace(1.0, 4.0, H))
    B_in = jax.random.normal(keys[2], (B, S, G, N))
    C_in = jax.random.normal(keys[3], (B, S, G, N))
    got_y, got_h = ref.ssd_chunked_ref(x, dt, a_log, B_in, C_in, chunk=16)
    want_y, want_h = ref.ssd_scan_ref(x, dt, a_log, B_in, C_in)
    _assert_close(got_y, want_y, jnp.float32)
    _assert_close(got_h, want_h, jnp.float32)


# --------------------------------------------------------------------- #
# RG-LRU scan
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("B,S,W,bs,bw", [
    (1, 64, 16, 16, 16),
    (2, 128, 48, 32, 16),
    (1, 96, 32, 32, 32),
])
def test_rglru_scan(B, S, W, bs, bw):
    k1, k2 = jax.random.split(jax.random.PRNGKey(7))
    a = jax.nn.sigmoid(jax.random.normal(k1, (B, S, W)))
    b = jax.random.normal(k2, (B, S, W))
    got = ops.rglru_scan(a, b, block_s=bs, block_w=bw)
    want, _ = ref.rglru_scan_ref(a, b)
    _assert_close(got, want, jnp.float32)


def test_rglru_assoc_scan_matches_sequential():
    """models.rglru associative scan == sequential reference."""
    import jax.numpy as jnp
    from repro.models.rglru import rglru_gates

    def combine(e1, e2):
        a1, b1 = e1
        a2, b2 = e2
        return a1 * a2, a2 * b1 + b2

    k1, k2 = jax.random.split(jax.random.PRNGKey(8))
    a = jax.nn.sigmoid(jax.random.normal(k1, (2, 64, 8)))
    b = jax.random.normal(k2, (2, 64, 8))
    _, h_assoc = jax.lax.associative_scan(combine, (a, b), axis=1)
    h_seq, _ = ref.rglru_scan_ref(a, b)
    _assert_close(h_assoc, h_seq, jnp.float32)

# --------------------------------------------------------------------- #
# interpret mode only on the CPU test platform
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("backend,interpret", [
    ("cpu", True), ("tpu", False), ("gpu", None)])
def test_kernels_interpret_only_on_cpu(monkeypatch, backend, interpret):
    monkeypatch.setattr(ops.jax, "default_backend", lambda: backend)
    if interpret is None:
        with pytest.raises(RuntimeError, match="'gpu' backend"):
            ops._interpret()
    else:
        assert ops._interpret() is interpret


# --------------------------------------------------------------------- #
# decode attention: argument validation (PR 9 satellite)
# --------------------------------------------------------------------- #
def test_decode_attention_validates_arguments():
    B, S, H, Hkv, D = 2, 64, 4, 2, 32
    keys = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(keys[0], (B, 1, H, D))
    kc = jax.random.normal(keys[1], (B, Hkv, S, D))
    vc = jax.random.normal(keys[2], (B, Hkv, S, D))
    lengths = jnp.full((B,), S)
    cases = [
        (dict(q=q[:, 0]), "must be \\(B, 1, H, D\\)"),            # 3-D q
        (dict(q=jnp.repeat(q, 2, axis=1)), "must be \\(B, 1, H, D\\)"),
        (dict(vc=vc[:, :, : S // 2]), "shapes differ"),
        (dict(q=q[:1]), "batch mismatch"),
        (dict(q=q[..., : D // 2]), "head dim mismatch"),
        (dict(kc=kc[:, :1], vc=vc[:, :1]),                        # Hkv=1 ok;
         None),                                                   # MQA valid
        (dict(kc=kc.repeat(3, axis=1),
              vc=vc.repeat(3, axis=1)), "multiple"),  # Hkv=6 does not divide H=4
        (dict(q=q.astype(jnp.bfloat16)), "dtype mismatch"),
        (dict(lengths=jnp.full((B, 1), S)), "lengths must be"),
    ]
    for override, match in cases:
        kw = dict(q=q, kc=kc, vc=vc, lengths=lengths)
        kw.update(override)
        if match is None:
            ops.decode_attention(kw["q"], kw["kc"], kw["vc"], kw["lengths"],
                                 block_kv=32)
            continue
        with pytest.raises(ValueError, match=match):
            ops.decode_attention(kw["q"], kw["kc"], kw["vc"], kw["lengths"],
                                 block_kv=32)


def test_decode_attention_rejects_unpadded_cache_length():
    from repro.kernels.decode_attention import decode_attention as raw
    B, S, H, Hkv, D = 1, 48, 2, 1, 16
    keys = jax.random.split(jax.random.PRNGKey(8), 3)
    q = jax.random.normal(keys[0], (B, 1, H, D))
    kc = jax.random.normal(keys[1], (B, Hkv, S, D))
    vc = jax.random.normal(keys[2], (B, Hkv, S, D))
    with pytest.raises(ValueError, match="multiple of\\s+block_kv"):
        raw(q, kc, vc, jnp.full((B,), S), block_kv=32, interpret=True)

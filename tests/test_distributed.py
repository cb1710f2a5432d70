"""shard_map sequence-parallel decode: multi-shard numerics via subprocess
(the main test process is pinned to 1 device; real sharding needs more)."""

import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np

from repro.launch.mesh import make_mesh_auto
from repro.serving.distributed import make_seqshard_decode_attn, reference_decode_attn


def test_single_shard_matches_reference(rng):
    mesh = make_mesh_auto((1,), ("data",))
    b, h, hk, d, n, r, g, m = 1, 4, 2, 16, 64, 8, 4, 8
    q = jnp.asarray(rng.standard_normal((b, h, d)), jnp.float32)
    a = jnp.asarray(rng.standard_normal((hk * d, r)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, n, hk, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, n, hk, d)), jnp.float32)
    k_lr = k.reshape(b, n, -1) @ a
    a3 = a.reshape(hk, d, r)
    q_lr = jnp.einsum("bhd,hdr->bhr", q, jnp.repeat(a3, h // hk, 0))
    k_new = jnp.asarray(rng.standard_normal((b, hk, d)), jnp.float32)
    v_new = jnp.asarray(rng.standard_normal((b, hk, d)), jnp.float32)
    length = jnp.int32(50)

    with mesh:
        fn = make_seqshard_decode_attn(mesh, axis="data", group_size=g,
                                       n_select=m, n_kv_heads=hk)
        got = fn(q, q_lr, k_lr, k, v, k_new, v_new, length)
    want = reference_decode_attn(q, q_lr, k_lr, k, v, k_new, v_new, length,
                                 group_size=g, n_select=m, n_shards=1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_multi_shard_matches_reference_subprocess():
    """Run the 4-shard case in a subprocess with 4 forced host devices."""
    prog = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import sys
        sys.path.insert(0, "src")
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh_auto
        from repro.serving.distributed import (make_seqshard_decode_attn,
                                               reference_decode_attn)
        rng = np.random.default_rng(0)
        mesh = make_mesh_auto((4,), ("data",))
        b, h, hk, d, n, r, g, m = 2, 8, 2, 16, 256, 8, 4, 16
        q = jnp.asarray(rng.standard_normal((b, h, d)), jnp.float32)
        a = jnp.asarray(rng.standard_normal((hk * d, r)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((b, n, hk, d)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((b, n, hk, d)), jnp.float32)
        k_lr = k.reshape(b, n, -1) @ a
        a3 = a.reshape(hk, d, r)
        q_lr = jnp.einsum("bhd,hdr->bhr", q, jnp.repeat(a3, h // hk, 0))
        k_new = jnp.asarray(rng.standard_normal((b, hk, d)), jnp.float32)
        v_new = jnp.asarray(rng.standard_normal((b, hk, d)), jnp.float32)
        length = jnp.int32(200)
        with mesh:
            fn = make_seqshard_decode_attn(mesh, axis="data", group_size=g,
                                           n_select=m, n_kv_heads=hk)
            got = jax.jit(fn)(q, q_lr, k_lr, k, v, k_new, v_new, length)
        want = reference_decode_attn(q, q_lr, k_lr, k, v, k_new, v_new, length,
                                     group_size=g, n_select=m, n_shards=4)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
        print("MULTISHARD_OK")
    """)
    out = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                         text=True, timeout=240, cwd=".")
    assert "MULTISHARD_OK" in out.stdout, out.stderr[-2000:]

"""Regression: chunked ``fetch_foreign_weights`` parity (multi-device
subprocess, like test_distributed.py).

The chunked path zero-pads the last dimension up to a multiple of
``fetch_chunk`` before the per-chunk einsum/all_to_all, and every source
contribution flows through ``mask / hosts_per_expert``. The hazard under
test: a padded tail that survives the mean-over-hosts reduction, or a
chunk-reassembly permutation, would silently corrupt the trailing columns
of fetched foreign experts — exactly the columns an odd ``d_ff`` leaves
past the last full chunk. So every cell uses an odd last dimension with a
non-dividing ``fetch_chunk`` and checks, elementwise:

* chunked output == unchunked (``fetch_chunk=0``) output, bit-exact in
  float32 (identical math, reordered only along sliced-off padding);
* both match a numpy oracle: mean over the expert's host rows — which is
  only non-trivial when ``hosts_per_expert > 1`` (E < G replication);
* ``-1`` foreign ids (unused slots) fetch exact zeros through both paths;
* a ``fetch_chunk`` larger than the last dimension degrades to the
  unchunked path (the guard, not a 1-chunk pad cycle).
"""
import os
import subprocess
import sys
import textwrap

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(body: str):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"       # the child never races for a chip
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(body)],
                       capture_output=True, text=True, env=env, timeout=900)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r.stdout


def test_fetch_chunked_padding_parity():
    out = _run("""
    import numpy as np, jax, jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P
    from repro.core.prefetch import fetch_foreign_weights
    from repro.core.topology import make_topology

    def cell(G, E, d, F, K, chunks, dtype=jnp.float32, with_empty=False):
        mesh = Mesh(np.array(jax.devices()[:G]), ("model",))
        topo = make_topology(G, E)
        epr = topo.experts_per_rank
        rng = np.random.default_rng(G * 100 + E)
        w = rng.normal(size=(G * epr, d, F)).astype(dtype)

        # K foreign experts per rank (never locally hosted); optionally
        # leave the last slot unused (-1) to cover the no-fetch path.
        fids = np.zeros((G, K), np.int32)
        for g in range(G):
            local = {int(e) for e in topo.slot_map[g]}
            cand = [e for e in range(E) if e not in local]
            fids[g] = (cand * K)[:K]
        if with_empty:
            fids[:, -1] = -1

        def run(chunk):
            def body(w_local):
                me = jax.lax.axis_index("model")
                return fetch_foreign_weights(
                    w_local, jnp.asarray(fids), me, topo,
                    axis_name="model", fetch_chunk=chunk)
            f = jax.shard_map(body, mesh=mesh, in_specs=P("model"),
                              out_specs=P("model"))
            with mesh:
                return np.asarray(jax.jit(f)(jnp.asarray(w)))

        # numpy oracle: dst g's k-th fetch = mean over the host rows
        ref = np.zeros((G * K, d, F), np.float64)
        w64 = w.astype(np.float64)
        for g in range(G):
            for k in range(K):
                e = int(fids[g, k])
                if e < 0:
                    continue                      # unused slot: zeros
                rows = [h * epr + int(np.argmax(topo.slot_map[h] == e))
                        for h in topo.host_of[e]]
                ref[g * K + k] = (sum(w64[r] for r in rows)
                                  / topo.hosts_per_expert)

        base = run(0)
        tol = 0.0 if dtype == jnp.float32 else 5e-2
        assert np.allclose(base.astype(np.float64), ref, atol=tol), \\
            f"unchunked vs oracle G={G} E={E}"
        for c in chunks:
            got = run(c)
            assert np.array_equal(got, base), \\
                f"chunk={c} diverged G={G} E={E} F={F}"
        print(f"cell G={G} E={E} F={F} hpe={topo.hosts_per_expert} ok")

    # hosts_per_expert > 1 (E < G): padded tail crosses the host mean
    cell(8, 4, 3, 7, 2, [3, 5], with_empty=True)   # hpe=2, odd F
    cell(8, 2, 2, 5, 1, [2, 3])                    # hpe=4
    # E > G (epr > 1): the common big-model shape, odd F again
    cell(4, 8, 3, 7, 2, [3, 4], with_empty=True)
    # fetch_chunk >= F takes the unchunked early-out, still exact
    cell(4, 2, 2, 7, 1, [7, 16])                   # hpe=2
    # low precision: pad/chunk reassembly must stay bit-identical even
    # when the 1/hosts_per_expert scale itself rounds
    cell(4, 2, 2, 7, 1, [4], dtype=jnp.bfloat16)
    print("OK")
    """)
    assert "OK" in out

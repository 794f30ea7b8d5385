"""Scheduler unit + property tests (paper Alg. 2 invariants)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # optional dep; deterministic fallback sampler
    from _hypothesis_shim import given, settings, strategies as st

from repro.core.scheduler import (even_split, initial_assign, rebalance,
                                  schedule)
from repro.core.topology import make_topology, static_opt_placement

jax.config.update("jax_platform_name", "cpu")


def test_paper_fig6_example():
    """3 GPUs, 15 tokens, expert loads (2, 4, 9) -> perfectly balanced 5/5/5
    (paper Figure 6)."""
    topo = make_topology(3, 3)
    counts = jnp.array([[1, 2, 2], [1, 1, 3], [0, 1, 4]], jnp.int32)
    S, diag = schedule(counts, topo, policy="harmoeny", q=1, c_pair=100,
                       num_foreign_slots=2)
    t_g = np.asarray(S.sum(axis=(0, 1)))
    assert t_g.tolist() == [5, 5, 5]
    assert (np.asarray(S.sum(axis=2)) == np.asarray(counts)).all()


def test_initial_assign_routes_to_hosts():
    topo = make_topology(4, 8)
    counts = jnp.full((4, 8), 3, jnp.int32)
    S = initial_assign(counts, topo)
    for e in range(8):
        host = int(topo.host_of[e, 0])
        assert int(S[:, e, host].sum()) == 12
        assert int(S[:, e, :].sum()) == 12


def test_initial_assign_replicated_split():
    """E < G: token load splits across an expert's host replicas."""
    topo = make_topology(4, 2)
    counts = jnp.array([[5, 0], [0, 0], [0, 0], [0, 0]], jnp.int32)
    S = initial_assign(counts, topo)
    hosts = topo.host_of[0]
    assert int(S[0, 0, hosts[0]]) == 3  # ceil split
    assert int(S[0, 0, hosts[1]]) == 2


def test_heavy_skew_balances():
    """90%-skew (paper §5.2): max load drops to ~average."""
    topo = make_topology(16, 64)
    counts = jnp.full((16, 64), 2, jnp.int32).at[:, 0].set(1000)
    S, diag = schedule(counts, topo, policy="harmoeny", q=4, c_pair=200,
                       num_foreign_slots=4)
    t_g = np.asarray(S.sum(axis=(0, 1)))
    avg = int(counts.sum()) // 16
    assert t_g.max() <= avg + 4
    assert int(diag.max_load_before) > 10 * int(diag.max_load_after)


def test_round_robin_keeps_initial():
    topo = make_topology(4, 8)
    counts = jnp.full((4, 8), 3, jnp.int32).at[0, 0].set(50)
    S, _ = schedule(counts, topo, policy="round_robin", q=1, c_pair=100,
                    num_foreign_slots=2)
    assert (np.asarray(S) == np.asarray(initial_assign(counts, topo))).all()


def test_even_split_uniform():
    topo = make_topology(4, 8)
    counts = jnp.full((4, 8), 8, jnp.int32)
    S = even_split(counts, topo)
    t_g = np.asarray(S.sum(axis=(0, 1)))
    assert (t_g == t_g[0]).all()
    assert (np.asarray(S.sum(axis=2)) == np.asarray(counts)).all()


def test_q_threshold_stops_small_moves():
    """Moves below q are not worth an expert fetch (paper Eq. 4)."""
    topo = make_topology(4, 8)
    counts = jnp.full((4, 8), 1, jnp.int32).at[0, 0].set(4)
    S, diag = schedule(counts, topo, policy="harmoeny", q=1000, c_pair=1000,
                       num_foreign_slots=2)
    assert int(diag.moved) == 0


def test_device_hardware_peaks_by_kind():
    """Eq. 4's peaks come from the running device's row; the CPU backend
    models a v5e, and a TPU kind without a row is an error."""
    from types import SimpleNamespace
    from repro.core.qthreshold import PEAKS, V5E, device_hardware
    assert device_hardware() is V5E                  # tests run on the CPU
    v5e = SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    assert device_hardware(v5e) is PEAKS["TPU v5 lite"] is V5E
    with pytest.raises(KeyError, match="TPU v9"):
        device_hardware(SimpleNamespace(platform="tpu", device_kind="TPU v9"))


def test_foreign_slot_budget():
    """No destination hosts more than K distinct non-resident experts."""
    topo = make_topology(4, 8)
    counts = jnp.zeros((4, 8), jnp.int32).at[:, :4].set(100)
    K = 1
    S, _ = schedule(counts, topo, policy="harmoeny", q=1, c_pair=1000,
                    num_foreign_slots=K)
    from repro.core.topology import local_slot_of
    lsl = local_slot_of(topo)
    S_np = np.asarray(S)
    for g in range(4):
        foreign = sum(1 for e in range(8)
                      if S_np[:, e, g].sum() > 0 and lsl[g, e] < 0)
        assert foreign <= K, (g, foreign)


def test_static_opt_placement_spreads_hot_experts():
    profile = np.array([100, 90, 80, 70, 1, 1, 1, 1], np.float64)
    perm = static_opt_placement(profile, 4)
    topo = make_topology(4, 8, placement=perm)
    hot_hosts = {int(topo.host_of[e, 0]) for e in range(4)}
    assert len(hot_hosts) == 4  # the four hot experts land on four ranks


# ----------------------------------------------------------------------
# Property-based invariants
# ----------------------------------------------------------------------
@settings(max_examples=30, deadline=None)
@given(st.integers(0, 2 ** 31 - 1), st.sampled_from([(4, 8), (4, 4), (8, 16)]),
       st.integers(1, 8), st.booleans())
def test_rebalance_properties(seed, gsh, q, skew):
    G, E = gsh
    rng = np.random.default_rng(seed)
    counts = rng.integers(0, 20, (G, E)).astype(np.int32)
    if skew:
        counts[:, 0] += rng.integers(50, 200)
    counts = jnp.asarray(counts)
    topo = make_topology(G, E)
    c_pair = max(int(2 * counts.sum()) // (G * G), 8)
    S0 = initial_assign(counts, topo)
    S, diag = rebalance(S0, topo, q=q, c_pair=c_pair, num_foreign_slots=4)
    S_np, S0_np = np.asarray(S), np.asarray(S0)
    # 1. conservation: scheduling never creates or destroys units
    assert (S_np.sum(axis=2) == np.asarray(counts)).all()
    # 2. non-negative
    assert (S_np >= 0).all()
    # 3. max destination load never increases
    assert S_np.sum(axis=(0, 1)).max() <= S0_np.sum(axis=(0, 1)).max()
    # 4. deterministic (replicated scheduling relies on this)
    S2, _ = rebalance(S0, topo, q=q, c_pair=c_pair, num_foreign_slots=4)
    assert (np.asarray(S2) == S_np).all()


def test_extra_local_spreads_without_foreign_slots():
    """A hot expert replicated on every rank (extra_local) can shed load to
    all of them with ZERO foreign slots — replica slots are weight-resident
    destinations, exactly like the expert's host."""
    topo = make_topology(4, 8)
    counts = jnp.zeros((4, 8), jnp.int32).at[:, 0].set(100)
    # without replication and K=0, nothing can move off expert 0's host
    S_none, d_none = schedule(counts, topo, policy="harmoeny", q=1,
                              c_pair=1000, num_foreign_slots=0)
    assert int(d_none.moved) == 0
    extra = jnp.zeros((4, 8), bool).at[:, 0].set(True)
    S, diag = schedule(counts, topo, policy="harmoeny", q=1, c_pair=1000,
                       num_foreign_slots=0, extra_local=extra)
    t_g = np.asarray(S.sum(axis=(0, 1)))
    assert t_g.tolist() == [100, 100, 100, 100]
    assert (np.asarray(S.sum(axis=2)) == np.asarray(counts)).all()


@settings(max_examples=30, deadline=None)
@given(st.integers(0, 2 ** 31 - 1), st.sampled_from([(4, 8), (8, 16)]),
       st.integers(1, 8), st.integers(0, 2))
def test_rebalance_extra_local_properties(seed, gsh, q, n_rep):
    """Alg. 2 invariants hold with replica-slot placements mixed in: the
    schedule stays conserved, non-negative, deterministic, and no worse
    than without the extra placements."""
    G, E = gsh
    rng = np.random.default_rng(seed)
    counts = rng.integers(0, 20, (G, E)).astype(np.int32)
    counts[:, 0] += rng.integers(50, 200)
    counts = jnp.asarray(counts)
    topo = make_topology(G, E)
    # replicate the n_rep hottest experts on every rank (a superset of any
    # real placement: hosts included — is_local is already True there)
    extra = np.zeros((G, E), bool)
    extra[:, :n_rep] = True
    extra = jnp.asarray(extra)
    c_pair = max(int(2 * counts.sum()) // (G * G), 8)
    S0 = initial_assign(counts, topo)
    S, _ = rebalance(S0, topo, q=q, c_pair=c_pair, num_foreign_slots=2,
                     extra_local=extra)
    S_np, S0_np = np.asarray(S), np.asarray(S0)
    assert (S_np.sum(axis=2) == np.asarray(counts)).all()
    assert (S_np >= 0).all()
    assert S_np.sum(axis=(0, 1)).max() <= S0_np.sum(axis=(0, 1)).max()
    S_plain, _ = rebalance(S0, topo, q=q, c_pair=c_pair, num_foreign_slots=2)
    # replication can only help: the balanced max load is no worse
    assert S_np.sum(axis=(0, 1)).max() \
        <= np.asarray(S_plain).sum(axis=(0, 1)).max()
    S2, _ = rebalance(S0, topo, q=q, c_pair=c_pair, num_foreign_slots=2,
                      extra_local=extra)
    assert (np.asarray(S2) == S_np).all()


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 2 ** 31 - 1))
def test_even_split_conservation(seed):
    rng = np.random.default_rng(seed)
    counts = jnp.asarray(rng.integers(0, 50, (4, 8)).astype(np.int32))
    topo = make_topology(4, 8)
    S = even_split(counts, topo)
    assert (np.asarray(S.sum(axis=2)) == np.asarray(counts)).all()
    t_g = np.asarray(S.sum(axis=(0, 1)))
    # remainders always land on the lowest-index ranks: worst-case spread is
    # one unit per (src, expert) pair
    assert t_g.max() - t_g.min() <= 4 * 8

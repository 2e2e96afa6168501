"""MINLP scheduler: CRA closed form, R-QAD relaxation, B&B vs brute force."""

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.baselines import (BASELINES, cloud_only, edge_first,
                                  greedy_assign, random_assign)
from repro.core.bnb import branch_and_bound, brute_force
from repro.core.cost import QueryTasks, SystemParams, assignment_cost, total_cost
from repro.core.cra import allocate_closed_form, o_total_calc
from repro.core.qad import (build_qad_arrays, round_relaxed, solve_rqad)
from repro.core.scheduler import schedule


def make_instance(N, K, seed=0, exec_prob=0.7):
    rng = np.random.default_rng(seed)
    params = SystemParams.synthetic(N, K, seed=seed)
    c = rng.uniform(1e7, 5e8, N)              # cycles
    w = rng.uniform(1e5, 5e7, N)              # bits
    e = (rng.random((N, K)) < exec_prob).astype(float) * params.assoc
    return QueryTasks(c=c, w=w, e=e), params


# -- CRA ----------------------------------------------------------------------

def test_cra_matches_scipy():
    from scipy.optimize import minimize
    rng = np.random.default_rng(1)
    N, K = 6, 2
    c = rng.uniform(1e6, 1e8, N)
    F = np.array([2e8, 3e8])
    De = np.zeros((N, K))
    De[:3, 0] = 1
    De[3:, 1] = 1
    f_closed = allocate_closed_form(De, c, F)
    o_closed = o_total_calc(De, c, F)

    # numeric optimum per edge server (normalized: f = x * F_k, obj scaled)
    for k in range(K):
        members = np.flatnonzero(De[:, k] > 0)
        cm = c[members] / c[members].max()

        def obj(x):
            return np.sum(cm / x)
        res = minimize(obj, np.full(len(members), 1.0 / len(members)),
                       constraints=[{"type": "ineq",
                                     "fun": lambda x: 1.0 - x.sum()}],
                       bounds=[(1e-6, 1.0)] * len(members), method="SLSQP")
        assert res.success
        assert np.allclose(res.x * F[k], f_closed[members, k], rtol=1e-3)
    # objective identity (Eq. 13)
    direct = sum(c[n] / f_closed[n, k] for k in range(K)
                 for n in np.flatnonzero(De[:, k] > 0))
    assert np.isclose(direct, o_closed, rtol=1e-12)


def test_cra_respects_capacity():
    tasks, params = make_instance(10, 3, seed=2)
    D = edge_first(tasks, params)
    f = allocate_closed_form(D * tasks.e * params.assoc, tasks.c, params.F)
    assert (f.sum(axis=0) <= params.F * (1 + 1e-9)).all()
    assert (f >= 0).all()


# -- R-QAD --------------------------------------------------------------------

def test_rqad_against_scipy():
    from scipy.optimize import minimize
    tasks, params = make_instance(5, 2, seed=3)
    e = tasks.e * params.assoc
    A, b, const = build_qad_arrays(tasks.c, tasks.w, e, params.r_edge,
                                   params.r_cloud)
    N, K = A.shape
    fixed_mask = np.zeros(N)
    fixed_D = np.zeros((N, K))
    D_rel, f_val, lb = solve_rqad(A, b, params.F, e, fixed_mask, fixed_D, 600)
    D_rel, f_val, lb = map(np.asarray, (D_rel, f_val, lb))

    def obj(x):
        D = x.reshape(N, K)
        S = (D * A).sum(axis=0)
        return (S ** 2 / params.F).sum() + (D * b).sum()

    cons = [{"type": "ineq",
             "fun": (lambda x, n=n: 1.0 - (x.reshape(N, K)[n] * e[n]).sum())}
            for n in range(N)]
    res = minimize(obj, np.zeros(N * K), bounds=[(0, 1)] * (N * K),
                   constraints=cons, method="SLSQP")
    assert f_val <= res.fun + 1e-6 * abs(res.fun) + 1e-9 or \
        np.isclose(f_val, res.fun, rtol=1e-4)
    # certified lower bound really is below both
    assert lb <= f_val + 1e-9
    assert lb <= res.fun + 1e-6 * abs(res.fun)


def test_rqad_feasibility_and_fixed_rows():
    tasks, params = make_instance(8, 3, seed=4)
    e = tasks.e * params.assoc
    A, b, const = build_qad_arrays(tasks.c, tasks.w, e, params.r_edge,
                                   params.r_cloud)
    fixed_mask = np.zeros(8)
    fixed_mask[:3] = 1
    fixed_D = np.zeros((8, 3))
    feas0 = np.flatnonzero(e[0] > 0)
    if len(feas0):
        fixed_D[0, feas0[0]] = 1.0
    D_rel, f_val, lb = solve_rqad(A, b, params.F, e, fixed_mask, fixed_D, 300)
    D_rel = np.asarray(D_rel)
    # constraints
    assert (D_rel >= -1e-9).all() and (D_rel <= 1 + 1e-9).all()
    assert ((D_rel * e).sum(axis=1) <= 1 + 1e-6).all()
    # fixed rows pinned
    assert np.allclose(D_rel[:3], fixed_D[:3])
    # e-infeasible coords zero
    assert np.allclose(D_rel[e == 0], 0.0)


def test_round_relaxed_feasible():
    D = np.array([[0.6, 0.3], [0.5, 0.5], [0.2, 0.1], [0.0, 0.9]])
    e = np.ones_like(D)
    R = round_relaxed(D, e)
    assert set(np.unique(R)) <= {0.0, 1.0}
    assert (R.sum(axis=1) <= 1).all()
    assert R[0, 0] == 1 and R[3, 1] == 1 and R[2].sum() == 0


# -- B&B ----------------------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 1, 2, 3, 4])
def test_bnb_matches_brute_force(seed):
    tasks, params = make_instance(6, 2, seed=seed)
    bf = brute_force(tasks, params)
    bb = branch_and_bound(tasks, params, solver_iters=300)
    assert bb.optimal
    assert np.isclose(bb.objective, bf.objective, rtol=1e-9), \
        f"bnb {bb.objective} vs brute {bf.objective}"


def test_bnb_best_first_matches_too():
    tasks, params = make_instance(5, 3, seed=7)
    bf = brute_force(tasks, params)
    bb = branch_and_bound(tasks, params, strategy="best_first")
    assert np.isclose(bb.objective, bf.objective, rtol=1e-9)


@pytest.mark.parametrize("seed", [0, 3, 11])
def test_bnb_rqad_bound_matches_brute_force(seed):
    """Paper-faithful bounding mode (convex relaxation) is exact too."""
    tasks, params = make_instance(5, 2, seed=seed)
    bf = brute_force(tasks, params)
    bb = branch_and_bound(tasks, params, bound="rqad", solver_iters=400,
                          warm_start="cloud", order="given")
    assert np.isclose(bb.objective, bf.objective, rtol=1e-9)


def test_bnb_fast_at_paper_scale():
    """N=20, K=4 (the paper's default) must schedule in well under a second."""
    tasks, params = make_instance(20, 4, seed=12)
    bb = branch_and_bound(tasks, params)
    assert bb.optimal
    assert bb.solve_seconds < 1.0, f"too slow: {bb.solve_seconds:.3f}s"


def test_bnb_beats_or_ties_baselines():
    tasks, params = make_instance(12, 3, seed=5)
    bb = branch_and_bound(tasks, params)
    for name, fn in BASELINES.items():
        D = fn(tasks, params)
        assert bb.objective <= assignment_cost(D, tasks, params) + 1e-9, name


def test_bnb_prunes():
    tasks, params = make_instance(10, 3, seed=6)
    bb = branch_and_bound(tasks, params)
    total_leaves = np.prod([1 + tasks.e[n].sum() for n in range(10)])
    assert bb.nodes_explored < total_leaves


def test_constraints_satisfied_all_policies():
    tasks, params = make_instance(15, 4, seed=8)
    for policy in ["bnb", "cloud_only", "random", "edge_first", "greedy"]:
        r = schedule(tasks, params, policy=policy)
        D = r.D
        assert set(np.unique(D)) <= {0.0, 1.0}                       # C1
        assert ((D * tasks.e * params.assoc).sum(axis=1) <= 1).all()  # C2
        assert (r.f >= 0).all()                                       # C3
        assert (r.f.sum(axis=0) <= params.F * (1 + 1e-9)).all()       # C4
        # objective consistency
        assert np.isclose(r.objective, assignment_cost(D, tasks, params),
                          rtol=1e-9)


def test_total_cost_consistency():
    tasks, params = make_instance(8, 2, seed=9)
    D = greedy_assign(tasks, params)
    f = allocate_closed_form(D * tasks.e * params.assoc, tasks.c, params.F)
    v1 = total_cost(D, f, tasks, params)
    v2 = assignment_cost(D, tasks, params)
    assert np.isclose(v1, v2, rtol=1e-9)


# -- property: B&B optimality on random tiny instances -------------------------

@given(st.integers(0, 10_000), st.integers(2, 5), st.integers(1, 3))
@settings(max_examples=15, deadline=None)
def test_bnb_optimal_property(seed, N, K):
    tasks, params = make_instance(N, K, seed=seed)
    bf = brute_force(tasks, params)
    bb = branch_and_bound(tasks, params)
    assert bb.objective <= bf.objective * (1 + 1e-9) + 1e-12
    assert bb.objective >= bf.objective * (1 - 1e-9) - 1e-12

"""Tests for vectormath/Solver, lang helpers, and PMML round-trip
(reference: VectorMathTest, LinearSystemSolverTest, ExecUtilsTest,
PMMLUtilsTest)."""

import threading

import numpy as np
import pytest

from oryx_tpu.common import lang, pmml, vectormath as vm


def test_dot_norm_cosine():
    x = np.array([1.0, 2.0, 3.0])
    y = np.array([4.0, 5.0, 6.0])
    assert vm.dot(x, y) == pytest.approx(32.0)
    assert vm.norm(x) == pytest.approx(np.sqrt(14.0))
    assert vm.cosine_similarity(x, x) == pytest.approx(1.0)
    assert vm.cosine_similarity(x, np.zeros(3)) == 0.0


def test_transpose_times_self():
    vecs = {1: np.array([1.0, 2.0]), 2: np.array([3.0, 4.0])}
    vtv = vm.transpose_times_self(vecs)
    np.testing.assert_allclose(vtv, np.array([[10.0, 14.0], [14.0, 20.0]]))
    assert vm.transpose_times_self({}) is None


def test_solver_solves_spd_system():
    a = np.array([[4.0, 1.0], [1.0, 3.0]])
    solver = vm.Solver(a)
    b = np.array([1.0, 2.0])
    x = solver.solve_f_to_f(b)
    np.testing.assert_allclose(a @ x, b, atol=1e-5)


def test_solver_rejects_singular():
    with pytest.raises(vm.SingularMatrixSolverException) as ei:
        vm.Solver(np.array([[1.0, 2.0], [2.0, 4.0]]))
    assert ei.value.apparent_rank == 1


@pytest.mark.parametrize("features, rows, seed", [(2, 10, 0), (50, 4000, 1), (250, 3000, 2)])
def test_solver_by_its_stored_inverse_equals_a_direct_solve(features, rows, seed):
    """The solver applies a stored float64 inverse (with one refinement
    step) where it used to hand LAPACK the factor for every right-hand
    side: the answers are `np.linalg.solve`'s on the full matrix."""
    rng = np.random.default_rng(seed)
    v = rng.standard_normal((rows, features))
    a = v.T @ v
    solver = vm.Solver(a)
    assert np.array_equal(solver.matrix, a)
    for _ in range(5):
        b = rng.standard_normal(features)
        want = np.linalg.solve(a, b)
        got = solver.solve_d_to_d(b)
        assert got.dtype == np.float64
        assert np.max(np.abs(got - want)) <= 1e-12 * np.max(np.abs(want))
        as_float = solver.solve_f_to_f(b.astype(np.float32))
        assert as_float.dtype == np.float32
        np.testing.assert_allclose(as_float, np.linalg.solve(a, b.astype(np.float32)), rtol=1e-5)


def test_solver_near_its_singularity_threshold_still_solves():
    """Condition 1e4 passes the rank check (threshold 1e-5 of the largest
    diagonal of R): the refinement step keeps a stored inverse at a direct
    solve's accuracy there too."""
    rng = np.random.default_rng(3)
    q, _ = np.linalg.qr(rng.standard_normal((40, 40)))
    a = q @ np.diag(np.logspace(0, -4, 40)) @ q.T
    a = (a + a.T) / 2
    solver = vm.Solver(a)
    b = rng.standard_normal(40)
    want = np.linalg.solve(a, b)
    assert np.max(np.abs(solver.solve_d_to_d(b) - want)) <= 1e-10 * np.max(np.abs(want))


@pytest.mark.parametrize("features", [3, 50])
def test_solver_still_raises_on_a_rank_deficient_matrix(features):
    rng = np.random.default_rng(4)
    v = rng.standard_normal((200, features))
    v[:, -1] = v[:, 0] + v[:, 1]  # one column is the sum of two others
    with pytest.raises(vm.SingularMatrixSolverException) as ei:
        vm.Solver(v.T @ v)
    assert ei.value.apparent_rank == features - 1
    with pytest.raises(vm.SingularMatrixSolverException) as ei:
        vm.Solver(np.zeros((features, features)))
    assert ei.value.apparent_rank == 0
    assert vm.get_solver(None) is None


def test_collect_in_parallel_ordered():
    out = lang.collect_in_parallel(10, lambda i: i * i, parallelism=4)
    assert out == [i * i for i in range(10)]


def test_collect_in_parallel_propagates_error():
    def fn(i):
        if i == 3:
            raise ValueError("boom")
        return i

    with pytest.raises(ValueError):
        lang.collect_in_parallel(5, fn, parallelism=2)


def test_rw_lock_excludes_writers():
    lock = lang.ReadWriteLock()
    state = {"writers": 0, "max_readers_during_write": 0}

    def writer():
        with lock.write():
            state["writers"] += 1
            assert state["writers"] == 1
            state["writers"] -= 1

    threads = [threading.Thread(target=writer) for _ in range(8)]
    with lock.read():
        for t in threads:
            t.start()
        # readers hold the lock; no writer can have entered yet
        assert state["writers"] == 0
    for t in threads:
        t.join()


def test_load_instance_of_with_and_without_args():
    inst = lang.load_instance_of("collections:OrderedDict")
    from collections import OrderedDict

    assert isinstance(inst, OrderedDict)
    lst = lang.load_instance_of("builtins:list", "ab")
    assert lst == ["a", "b"]


def test_pmml_round_trip(tmp_path):
    root = pmml.build_skeleton_pmml()
    model = pmml.sub(root, "ClusteringModel", {"modelName": "test", "functionName": "clustering"})
    pmml.sub(model, "Extension", {"name": "k", "value": "3"})
    path = tmp_path / "model.pmml"
    pmml.write_pmml(root, path)
    again = pmml.read_pmml(path)
    cm = pmml.find(again, "ClusteringModel")
    assert cm is not None
    assert cm.get("modelName") == "test"
    ext = pmml.find(again, "ClusteringModel/Extension")
    assert ext.get("value") == "3"
    # string round trip
    text = pmml.to_string(root)
    assert pmml.find(pmml.from_string(text), "ClusteringModel") is not None


def test_pmml_header_has_app_and_timestamp():
    root = pmml.build_skeleton_pmml("myapp")
    app = pmml.find(root, "Header/Application")
    assert app.get("name") == "myapp"
    assert pmml.find(root, "Header/Timestamp").text

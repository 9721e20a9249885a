"""The paper's contribution: GBDT/DT/SVM learners, dataset construction,
selector dispatch, paper-metric computation."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro import core
from repro.core.gbdt import DecisionTreeClassifier, GBDTClassifier, GBDTRegressor
from repro.core.svm import SVMClassifier


def _xor_data(n=200, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.rand(n, 2)
    y = np.where((X[:, 0] > 0.5) ^ (X[:, 1] > 0.5), 1, -1)
    return X, y


class TestLearners:
    def test_gbdt_learns_xor(self):
        X, y = _xor_data()
        clf = GBDTClassifier(n_estimators=8, max_depth=8, eta=1.0).fit(X, y)
        assert (clf.predict(X) == y).mean() >= 0.98

    def test_dt_learns_xor(self):
        X, y = _xor_data()
        clf = DecisionTreeClassifier(max_depth=8).fit(X, y)
        assert (clf.predict(X) == y).mean() >= 0.95

    def test_svm_rbf_learns_xor(self):
        X, y = _xor_data(120)
        clf = SVMClassifier(C=1000.0, kernel="rbf", gamma=10.0).fit(X, y)
        assert (clf.predict(X) == y).mean() >= 0.9

    def test_gbdt_regressor(self):
        rng = np.random.RandomState(0)
        X = rng.rand(300, 3)
        y = 2 * X[:, 0] - X[:, 1] + 0.5 * X[:, 2]
        reg = GBDTRegressor(n_estimators=50, max_depth=4, eta=0.3).fit(X, y)
        err = np.abs(reg.predict(X) - y).mean()
        assert err < 0.1

    def test_gbdt_depth_bound(self):
        """Paper: prediction is O(h) — trained trees respect max_depth."""
        X, y = _xor_data()
        clf = GBDTClassifier(n_estimators=4, max_depth=3).fit(X, y)
        assert all(t.root.depth() <= 3 for t in clf.trees)

    def test_gbdt_persistence_roundtrip(self, tmp_path):
        X, y = _xor_data()
        clf = GBDTClassifier().fit(X, y)
        p = str(tmp_path / "m.json")
        clf.save(p)
        clf2 = GBDTClassifier.load(p)
        np.testing.assert_array_equal(clf.predict(X), clf2.predict(X))


class TestDataset:
    def test_analytic_dataset_structure(self):
        ds = core.collect_analytic(lo=7, hi=10)
        # paper's 8-dim features + the op-kind and batch-extent columns
        # (all-NT, all-g=1 here)
        assert ds.X.shape[1] == 10
        assert (ds.X[:, 8] == 0.0).all()
        assert (ds.X[:, 9] == 1.0).all()
        assert set(np.unique(ds.y)) <= {-1, 1}
        assert len(ds) == len(ds.mnk) == len(ds.hw)
        # both classes present (the tradeoff is real)
        c = ds.class_counts()
        assert c[-1] > 0 and c[1] > 0

    def test_oom_filter(self):
        """Paper: TNN samples that don't fit device memory are dropped."""
        ds_full = core.collect_analytic(lo=7, hi=16, chips=[core.TPU_V5E])
        assert len(ds_full) < 1000  # paper: 891/941 valid of 1000

    def test_label_consistency(self):
        """label == sign(P_NT - P_TNN) == sign(t_TNN - t_NT)."""
        ds = core.collect_analytic(lo=7, hi=10)
        want = np.where(ds.times["NT"] <= ds.times["TNN"], 1, -1)
        np.testing.assert_array_equal(ds.y, want)

    def test_dataset_roundtrip(self, tmp_path):
        ds = core.collect_analytic(lo=7, hi=9)
        p = str(tmp_path / "ds.npz")
        ds.save(p)
        ds2 = core.SelectionDataset.load(p)
        np.testing.assert_array_equal(ds.y, ds2.y)
        np.testing.assert_allclose(ds.times["TNN"], ds2.times["TNN"])

    def test_measured_dataset_small(self):
        ds = core.collect_measured(sizes=[32, 64], reps=1)
        assert len(ds) == 8
        assert (ds.times["NT"] > 0).all() and (ds.times["TNN"] > 0).all()


class TestTrainingPipeline:
    def setup_method(self):
        self.ds = core.collect_analytic(lo=7, hi=11)

    def test_split_stratified(self):
        tr, te = core.train_test_split(self.ds, 0.8)
        assert abs(len(tr) - 0.8 * len(self.ds)) <= len(np.unique(self.ds.hw))
        # per-hardware stratification
        for hw in np.unique(self.ds.hw):
            n_tr = (tr.hw == hw).sum()
            n_all = (self.ds.hw == hw).sum()
            assert abs(n_tr - 0.8 * n_all) <= 1

    def test_cv_accuracy_band(self):
        cv = core.kfold_cv(self.ds, "gbdt")
        assert cv["total"]["avg"] > 0.85  # paper: 90.51%

    def test_selection_metrics_properties(self):
        clf, report = core.train_paper_model(self.ds)
        m = report["selection"]
        # GOW >= 0, LUB <= 0 by definition; oracle-consistency
        assert m["gow_avg"] >= 0 and m["gow_max"] >= m["gow_avg"]
        assert m["lub_avg"] <= 0 and m["lub_min"] <= m["lub_avg"]
        # selector never below both arms, never above best
        assert m["mtnn_vs_nt"] >= m["lub_avg"]

    def test_oracle_predictor_metrics(self):
        """A perfect predictor: LUB == 0 and MTNN-vs-NT == oracle gain."""
        m = core.selection_metrics(self.ds, self.ds.y)
        assert m["lub_avg"] == 0.0 and m["lub_min"] == 0.0
        assert m["gow_avg"] > 0

    def test_accuracy_vs_train_size_monotone_ish(self):
        curve = core.accuracy_vs_train_size(self.ds, fracs=(0.1, 0.5, 1.0))
        accs = [a for _, a in curve]
        assert accs[-1] >= accs[0] - 0.02  # paper Fig.4: grows with data
        assert accs[-1] > 0.9

    def test_kway_model(self):
        model, report = core.train_kway_model(self.ds)
        assert report["oracle_match"] > 0.7
        assert report["mean_slowdown_vs_oracle"] < 1.2


class TestSelector:
    def setup_method(self):
        ds = core.collect_analytic(lo=7, hi=11)
        clf, _ = core.train_paper_model(ds)
        self.sel = core.MTNNSelector(clf)

    def test_select_returns_candidate(self):
        name = self.sel.select(core.OpKey("NT", 1024, 1024, 1024))
        assert name in core.CANDIDATES

    def test_oom_guard_falls_back_to_nt(self):
        """Paper: if B^T does not fit, use NT."""
        huge = 2**22
        key = core.OpKey("NT", huge, huge, 4096, 4)
        assert self.sel.select(key) == self.sel.binary_pair[0]

    def test_selection_caching(self):
        self.sel.select(core.OpKey("NT", 512, 512, 512))
        n0 = self.sel.stats.calls
        self.sel.select(core.OpKey("NT", 512, 512, 512))
        assert self.sel.stats.calls == n0 + 1  # cached, still counted

    def test_dispatch_correctness(self):
        a = jnp.asarray(np.random.RandomState(0).randn(33, 20), jnp.float32)
        b = jnp.asarray(np.random.RandomState(1).randn(17, 20), jnp.float32)
        with core.use_policy(core.ModelPolicy(self.sel)):
            out = core.dispatch("NT", a, b)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(a) @ np.asarray(b).T, rtol=1e-5, atol=1e-5
        )

    def test_dispatch_leading_dims(self):
        a = jnp.ones((2, 3, 8), jnp.float32)
        b = jnp.ones((5, 8), jnp.float32)
        with core.use_policy(core.ModelPolicy(self.sel)):
            out = core.dispatch("NT", a, b)
        assert out.shape == (2, 3, 5)

    def test_force_override(self):
        a, b = jnp.ones((4, 8)), jnp.ones((3, 8))
        for name, cand in core.CANDIDATES.items():
            if "NT" not in cand.ops:
                continue
            with core.use_policy(core.FixedPolicy(name)):
                out = core.dispatch("NT", a, b)
            np.testing.assert_allclose(np.asarray(out), 8.0)

    def test_selector_persistence(self, tmp_path):
        p = str(tmp_path / "sel.json")
        self.sel.save(p)
        sel2 = core.MTNNSelector.load(p)
        for mnk in [(128, 128, 128), (8192, 8192, 8192), (1024, 65536, 256)]:
            key = core.OpKey("NT", *mnk)
            assert self.sel.select(key) == sel2.select(key)

    def test_distributed_mode_restricts_candidates(self):
        sel = core.MTNNSelector(self.sel.model, distributed=True)
        for mnk in [(128, 128, 128), (4096, 4096, 4096), (65536, 512, 65536)]:
            name = sel.select(core.OpKey("NT", *mnk))
            assert core.CANDIDATES[name].distributed_safe


class TestDeviceSpec:
    @staticmethod
    def _dev(platform, kind):
        import types

        return types.SimpleNamespace(platform=platform, device_kind=kind)

    def test_v5e_kind_maps_to_its_spec(self):
        assert core.device_spec(self._dev("tpu", "TPU v5 lite")) is core.TPU_V5E

    @pytest.mark.parametrize("kind", ["TPU v9 mega", "TPU v5 lite "])
    def test_unknown_tpu_kind_raises(self, kind):
        with pytest.raises(ValueError, match="no HardwareSpec"):
            core.device_spec(self._dev("tpu", kind))

    def test_cpu_maps_to_host_spec(self):
        assert core.device_spec(self._dev("cpu", "cpu")) == core.host_spec()

    def test_target_spec_on_cpu_models_the_v5e(self):
        # kernels run in interpret mode here as a rehearsal of the v5e path
        assert core.target_spec() is core.TPU_V5E

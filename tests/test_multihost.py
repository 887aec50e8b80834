"""Multi-host mesh mapping (the Linkers rendezvous role,
reference src/network/linkers_socket.cpp:165-220 -> jax.distributed).

TestMultihostMapping covers the config-mapping logic in-process; the
TestTwoProcessRendezvous smoke test spawns a REAL 2-process
jax.distributed group (gloo CPU collectives) that runs init_multihost ->
global 8-device mesh -> one data-parallel tree, asserting identical
split records on both ranks — the automated stand-in for the reference's
manual parallel_learning runbook (linkers_socket.cpp:165-220).
"""

import os
import socket
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from lightgbm_tpu.parallel import mesh


class TestMultihostMapping:
    def test_single_machine_skips(self):
        assert mesh.init_multihost("", 0, 1) is False
        assert mesh.init_multihost("127.0.0.1:12400", 12400, 1) is False

    def test_unresolvable_process_id_raises(self, monkeypatch):
        monkeypatch.delenv("LIGHTGBM_TPU_HOST_IP", raising=False)
        monkeypatch.delenv("LIGHTGBM_TPU_PROCESS_ID", raising=False)
        with pytest.raises(ValueError, match="position"):
            mesh.init_multihost("10.0.0.1:12400,10.0.0.2:12400", 12400, 2)

    def test_process_id_from_host_ip(self, monkeypatch):
        """The pid resolution finds this host in the machine list; the
        jax.distributed.initialize call itself is stubbed (no cluster)."""
        calls = {}

        def fake_init(coordinator_address, num_processes, process_id):
            calls.update(coordinator=coordinator_address,
                         n=num_processes, pid=process_id)

        import jax

        monkeypatch.setattr(jax.distributed, "initialize", fake_init)
        monkeypatch.setenv("LIGHTGBM_TPU_HOST_IP", "10.0.0.2")
        mesh._distributed_initialized = False
        try:
            assert mesh.init_multihost(
                "10.0.0.1:12400,10.0.0.2:12400,10.0.0.3:12400", 12400, 3)
            assert calls == {"coordinator": "10.0.0.1:12400", "n": 3,
                             "pid": 1}
        finally:
            mesh._distributed_initialized = False


_WORKER_SRC = """
import os, sys
root = {root!r}
sys.path.insert(0, root)
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax
jax.config.update("jax_cpu_collectives_implementation", "gloo")
import numpy as np
from lightgbm_tpu.config import Config
from lightgbm_tpu.io.dataset import TrainingData
from lightgbm_tpu.models.learner import TPUTreeLearner

pid = int(os.environ["LIGHTGBM_TPU_PROCESS_ID"])
# every rank loads the SAME data (the reference's all-data-on-all-machines
# mode; pre-partitioned loading is a separate path)
rng = np.random.default_rng(7)
X = rng.normal(size=(2048, 10))
y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.float64)
cfg = Config({{"objective": "binary", "max_bin": 16, "num_leaves": 7,
              "min_data_in_leaf": 5, "tpu_block_rows": 256,
              "tree_learner": "data", "num_machines": 8,
              "machines": {machines!r}}})
td = TrainingData.from_matrix(X, y, cfg)
learner = TPUTreeLearner(cfg, td)   # init_multihost runs in here
assert jax.process_count() == 2, jax.process_count()
assert len(jax.devices()) == 8
grad = rng.normal(size=2048).astype(np.float32)
hess = np.abs(rng.normal(size=2048)).astype(np.float32) + 0.1
tree, _, out = learner.train(grad, hess)
rec = np.asarray(jax.device_get(out["records"]))
assert rec[0, 14] > 0.5, "no split grown"
np.save({outfile!r}, rec)
print(f"rank {{pid}}: {{int(rec[:, 14].sum())}} splits", flush=True)

# FULL training through the public API on the same global mesh: the GBDT
# driver routes multi-process learners through the sync path (local score
# state, allgathered leaf ids) — every rank must produce the same model
import lightgbm_tpu as lgb
ds = lgb.Dataset(X, label=y, params=dict(cfg.params))
bst = lgb.train({{**dict(cfg.params), "verbosity": -1,
                 "num_iterations": 3}}, ds, num_boost_round=3)
model = bst.model_to_string().split("\\nparameters:")[0]
with open({outfile!r} + ".model", "w") as f:
    f.write(model)
print(f"rank {{pid}}: trained {{bst.num_trees()}} trees", flush=True)

# ---- distributed metrics + early stopping on a PARTITIONED valid set:
# each rank holds only HALF the validation rows, so a host-local metric
# would differ across ranks; the metric_sync reduction must make every
# rank report the GLOBAL value and stop at the SAME iteration
import json
rngv = np.random.default_rng(21)
Xv = rngv.normal(size=(1024, 10))
yv = (Xv[:, 0] + 0.5 * Xv[:, 1]
      + rngv.normal(scale=0.7, size=1024) > 0).astype(np.float64)
half = 512
lo, hi = pid * half, (pid + 1) * half
p_es = dict(cfg.params)
p_es["verbosity"] = -1
p_es["num_iterations"] = 12
p_es["metric"] = ["binary_logloss", "auc"]
dtr = lgb.Dataset(X, label=y, params=p_es)
dval = lgb.Dataset(Xv[lo:hi], label=yv[lo:hi], reference=dtr, params=p_es)
hist = {{}}
bst3 = lgb.train(p_es, dtr, num_boost_round=12,
                 valid_sets=[dval], valid_names=["part"],
                 callbacks=[lgb.early_stopping(2, verbose=False),
                            lgb.record_evaluation(hist)])
n_it = bst3.current_iteration()
# independent expected values: plain numpy on the FULL valid set (no
# collectives, identical on both ranks), predictions from the model
margin = bst3.predict(Xv, num_iteration=n_it, raw_score=True)
pm = np.clip(1.0 / (1.0 + np.exp(-margin)), 1e-15, 1.0 - 1e-15)
exp_ll = float(-(yv * np.log(pm) + (1.0 - yv) * np.log(1.0 - pm)).mean())
order = np.argsort(margin, kind="stable")
ss = margin[order]
pos = (yv[order] > 0).astype(np.float64)
neg = 1.0 - pos
bnd = np.flatnonzero(np.diff(ss)) + 1
gid = np.zeros(len(ss), np.int64)
gid[bnd] = 1
gid = np.cumsum(gid)
ng = int(gid[-1]) + 1
posg = np.bincount(gid, weights=pos, minlength=ng)
negg = np.bincount(gid, weights=neg, minlength=ng)
negb = np.concatenate([[0.0], np.cumsum(negg)[:-1]])
exp_auc = float((posg * (negb + 0.5 * negg)).sum()
                / (pos.sum() * neg.sum()))
rec2 = {{"best_iter": int(bst3.best_iteration),
         "n_iter": int(n_it),
         "curve_ll": hist["part"]["binary_logloss"],
         "curve_auc": hist["part"]["auc"],
         "expected_ll": exp_ll, "expected_auc": exp_auc}}
with open({outfile!r} + ".esjson", "w") as f:
    json.dump(rec2, f)
print(f"rank {{pid}}: es best_iter={{bst3.best_iteration}}", flush=True)

# ---- pre-partitioned TRAINING rows (reference loader pre_partition):
# each rank holds only its HALF of the training rows; bin finding runs
# feature-sharded + allgather, rows place as process-local shards, and
# metrics/boost-from-average reduce globally.  Deterministic f64 with
# identical global row order => the model must BIT-match a serial
# full-data run in the same bin space.
p_pt = dict(cfg.params)
# boost_from_average=false: the distributed init is the MEAN of the
# per-rank inits (reference GlobalSyncUpByMean), which legitimately
# differs from a centralized full-data init on imbalanced halves —
# bit-matching serial requires removing that known semantic difference
p_pt.update(verbosity=-1, deterministic=True, pre_partition=True,
            metric=["auc"], tpu_shape_buckets=0, num_iterations=3,
            boost_from_average=False)
half_t = 1024
ds_pt = lgb.Dataset(X[pid * half_t:(pid + 1) * half_t],
                    label=y[pid * half_t:(pid + 1) * half_t],
                    params=p_pt)
bst_pt = lgb.train(p_pt, ds_pt, num_boost_round=3,
                   keep_training_booster=True)
m_pt = bst_pt.model_to_string().split("\\nparameters:")[0]
auc_pt = dict((nm, v) for _, nm, v, _ in bst_pt.eval_train())["auc"]
# serial full-data reference in the SAME bin space (shared mappers)
p_sr = {{k: v for k, v in p_pt.items()
         if k not in ("machines", "num_machines", "pre_partition")}}
p_sr["tree_learner"] = "serial"
ds_sr = lgb.Dataset(X, label=y, reference=ds_pt, params=p_sr)
bst_sr = lgb.train(p_sr, ds_sr, num_boost_round=3,
                   keep_training_booster=True)
m_sr = bst_sr.model_to_string().split("\\nparameters:")[0]
auc_sr = dict((nm, v) for _, nm, v, _ in bst_sr.eval_train())["auc"]

# psum partial-sum order differs from the serial block scan by f64
# ulps, and the f32 leaf-value downcast can flip at a rounding
# boundary — so the contract is STRUCTURAL exactness (every split
# line identical) + numeric closeness on the value lines
def split_lines(m):
    keep = ("split_feature=", "threshold=", "left_child=", "right_child=")
    out = [l for l in m.splitlines() if l.startswith(keep)]
    for l in m.splitlines():
        # default-left (bit 2) may flip on direction-gain ties under a
        # different reduction order; everything else must be identical
        if l.startswith("decision_type="):
            out.append(" ".join(str(int(v) & ~2)
                                for v in l.split("=")[1].split()))
    return out
def value_rows(m):
    out = []
    for l in m.splitlines():
        if l.startswith(("leaf_value=", "internal_value=",
                         "split_gain=")):
            out.extend(float(v) for v in l.split("=")[1].split())
    return np.asarray(out)
struct_ok = split_lines(m_pt) == split_lines(m_sr)
v_pt, v_sr = value_rows(m_pt), value_rows(m_sr)
val_delta = (float(np.max(np.abs(v_pt - v_sr)))
             if len(v_pt) == len(v_sr) else float("inf"))
with open({outfile!r} + ".ptmodel", "w") as f:
    f.write(m_pt)
with open({outfile!r} + ".srmodel", "w") as f:
    f.write(m_sr)
with open({outfile!r} + ".ptjson", "w") as f:
    json.dump({{"auc_pt": auc_pt, "auc_sr": auc_sr,
               "struct_ok": bool(struct_ok),
               "val_delta": val_delta}}, f)
print(f"rank {{pid}}: partitioned-train auc={{auc_pt:.4f}} "
      f"struct_ok={{struct_ok}} val_delta={{val_delta:.2e}}", flush=True)

# ---- sparse COO storage x pre_partition: the sparse-feature decision
# comes from GLOBAL nonzero fractions, each process builds only its own
# shards' tables, and the partitioned model must structurally match a
# serial-sparse full-data run in the same bin space
rngs = np.random.default_rng(33)
Xs_full = np.zeros((2048, 12))
Xs_full[:, :4] = rngs.normal(size=(2048, 4))
for f in range(4, 12):
    nzr = rngs.choice(2048, size=64, replace=False)
    Xs_full[nzr, f] = rngs.normal(size=64) + 1.0
ys_full = (Xs_full[:, 0] + 2.0 * Xs_full[:, 5] > 0).astype(np.float64)
p_sp = dict(p_pt)
p_sp.update(enable_bundle=False, tpu_sparse_threshold=0.2,
            num_iterations=2)
# scipy ingest composes with the distributed (feature-sharded) bin
# finding: the CSC columns ride the same collective as dense input
import scipy.sparse as sps
ds_sp = lgb.Dataset(sps.csr_matrix(Xs_full[pid * half_t:(pid + 1) * half_t]),
                    label=ys_full[pid * half_t:(pid + 1) * half_t],
                    params=p_sp)
bst_sp = lgb.train(p_sp, ds_sp, num_boost_round=2,
                   keep_training_booster=True)
assert bst_sp._driver.learner.params.has_sparse, "sparse did not engage"
m_sp = bst_sp.model_to_string().split("\\nparameters:")[0]
p_ss = {{k: v for k, v in p_sp.items()
         if k not in ("machines", "num_machines", "pre_partition")}}
p_ss["tree_learner"] = "serial"
ds_ss = lgb.Dataset(Xs_full, label=ys_full, reference=ds_sp, params=p_ss)
bst_ss = lgb.train(p_ss, ds_ss, num_boost_round=2,
                   keep_training_booster=True)
m_ss = bst_ss.model_to_string().split("\\nparameters:")[0]
sp_struct = split_lines(m_sp) == split_lines(m_ss)
v_sp, v_ss = value_rows(m_sp), value_rows(m_ss)
sp_delta = (float(np.max(np.abs(v_sp - v_ss)))
            if len(v_sp) == len(v_ss) else float("inf"))
with open({outfile!r} + ".spjson", "w") as f:
    json.dump({{"struct_ok": bool(sp_struct), "val_delta": sp_delta,
               "model": m_sp}}, f)
print(f"rank {{pid}}: sparse x pre_partition struct_ok={{sp_struct}} "
      f"val_delta={{sp_delta:.2e}}", flush=True)

# ---- GOSS x pre_partition: the threshold/sample run over LOCAL rows
# (the reference's distributed behavior — each machine subsets its own
# data); every rank must still produce the identical global model
p_go = dict(p_pt)
p_go.update(boosting="goss", top_rate=0.3, other_rate=0.2,
            learning_rate=1.0, num_iterations=3)
ds_go = lgb.Dataset(X[pid * half_t:(pid + 1) * half_t],
                    label=y[pid * half_t:(pid + 1) * half_t],
                    params=p_go)
bst_go = lgb.train(p_go, ds_go, num_boost_round=3)
m_go = bst_go.model_to_string().split("\\nparameters:")[0]
with open({outfile!r} + ".gossmodel", "w") as f:
    f.write(m_go)
print(f"rank {{pid}}: goss x pre_partition trained "
      f"{{bst_go.num_trees()}} trees", flush=True)

# ---- lambdarank x pre_partition: per-query lambdas run over LOCAL
# queries (queries live whole on one rank — the reference's distributed
# ranking semantics), histograms aggregate globally, and the NDCG train
# metric reduces across ranks.  Deterministic f64: structural parity
# with serial full-data training, identical global NDCG.
rngr = np.random.default_rng(44)
Xr2 = rngr.normal(size=(2048, 10))
rel2 = np.minimum((np.abs(Xr2[:, 0]) * 2).astype(np.int64), 3)
qsz = 16
p_lr = dict(p_pt)
p_lr.update(objective="lambdarank", metric=["ndcg"], eval_at=[3],
            num_iterations=2, label_gain=",".join(
                str((1 << i) - 1) for i in range(4)))
ds_lr = lgb.Dataset(Xr2[pid * half_t:(pid + 1) * half_t],
                    label=rel2[pid * half_t:(pid + 1) * half_t],
                    group=[qsz] * (half_t // qsz), params=p_lr)
bst_lr = lgb.train(p_lr, ds_lr, num_boost_round=2,
                   keep_training_booster=True)
m_lr = bst_lr.model_to_string().split("\\nparameters:")[0]
ndcg_lr = bst_lr.eval_train()[0][2]
p_ls = {{k: v for k, v in p_lr.items()
         if k not in ("machines", "num_machines", "pre_partition")}}
p_ls["tree_learner"] = "serial"
ds_ls = lgb.Dataset(Xr2, label=rel2, group=[qsz] * (2048 // qsz),
                    reference=ds_lr, params=p_ls)
bst_ls = lgb.train(p_ls, ds_ls, num_boost_round=2,
                   keep_training_booster=True)
m_ls = bst_ls.model_to_string().split("\\nparameters:")[0]
ndcg_ls = bst_ls.eval_train()[0][2]
lr_struct = split_lines(m_lr) == split_lines(m_ls)
with open({outfile!r} + ".lrjson", "w") as f:
    json.dump({{"struct_ok": bool(lr_struct),
               "ndcg_pt": ndcg_lr, "ndcg_sr": ndcg_ls}}, f)
print(f"rank {{pid}}: lambdarank x pre_partition struct_ok={{lr_struct}} "
      f"ndcg={{ndcg_lr:.4f}}", flush=True)

# ---- percentile-renew x pre_partition: each rank refits leaf outputs
# from its LOCAL rows' percentiles; the driver then averages per leaf
# over contributing machines (the reference's GlobalSum scheme,
# serial_tree_learner.cpp:865-891).  Both ranks must agree bitwise and
# the l1 train metric (globally reduced) must beat the constant model.
p_q = dict(p_pt)
p_q.update(objective="regression_l1", metric=["l1"], num_iterations=3,
           learning_rate=0.5)
yq = X[:, 0] * 2.0 + 0.3 * rng.normal(size=2048)
ds_q = lgb.Dataset(X[pid * half_t:(pid + 1) * half_t],
                   label=yq[pid * half_t:(pid + 1) * half_t],
                   params=p_q)
bst_q = lgb.train(p_q, ds_q, num_boost_round=3,
                  keep_training_booster=True)
m_q = bst_q.model_to_string().split("\\nparameters:")[0]
l1_q = bst_q.eval_train()[0][2]
base_l1 = float(np.abs(yq - np.median(yq)).mean())
with open({outfile!r} + ".qjson", "w") as f:
    json.dump({{"model": m_q, "l1": l1_q, "base_l1": base_l1}}, f)
print(f"rank {{pid}}: renew x pre_partition l1={{l1_q:.4f}} "
      f"(const model {{base_l1:.4f}})", flush=True)

# ---- EFB x pre_partition: the bundling plan is found from a globally
# allgathered row sample (and globally reduced zero fractions), so
# every rank greedy-groups identically; with the full data inside the
# sample quota the plan equals the serial full-data one -> structural
# parity in deterministic f64
rngb = np.random.default_rng(55)
Xb = np.zeros((2048, 10))
Xb[:, :2] = rngb.normal(size=(2048, 2))
owner = rngb.integers(2, 10, size=2048)
for f in range(2, 10):
    rows_f = np.flatnonzero(owner == f)
    # strictly positive stored values keep 0.0 in bin 0 (the
    # bundling heuristic keys on the bin-0 default) and a handful of
    # DISTINCT levels keeps each feature's bin count small enough for
    # several features to share one bundle's bin budget
    Xb[rows_f, f] = rngb.integers(1, 6, size=len(rows_f)).astype(float)
yb = ((Xb[:, 0] > 0) ^ (owner % 2 == 0)).astype(np.float64)
p_b = dict(p_pt)
# max_bin=64: at the worker default of 16 a bundle cannot hold two
# 16-bin features (budget is max_bundle_bins-1), so no plan would form
p_b.update(enable_bundle=True, num_iterations=2, max_bin=64)
ds_b = lgb.Dataset(Xb[pid * half_t:(pid + 1) * half_t],
                   label=yb[pid * half_t:(pid + 1) * half_t], params=p_b)
bst_b = lgb.train(p_b, ds_b, num_boost_round=2,
                  keep_training_booster=True)
assert bst_b._driver.learner.bundle_plan is not None, "EFB did not engage"
m_b = bst_b.model_to_string().split("\\nparameters:")[0]
p_bs = {{k: v for k, v in p_b.items()
         if k not in ("machines", "num_machines", "pre_partition")}}
p_bs["tree_learner"] = "serial"
ds_bs = lgb.Dataset(Xb, label=yb, reference=ds_b, params=p_bs)
bst_bs = lgb.train(p_bs, ds_bs, num_boost_round=2,
                   keep_training_booster=True)
m_bs = bst_bs.model_to_string().split("\\nparameters:")[0]
b_struct = split_lines(m_b) == split_lines(m_bs)
with open({outfile!r} + ".efbjson", "w") as f:
    json.dump({{"struct_ok": bool(b_struct), "model": m_b}}, f)
print(f"rank {{pid}}: efb x pre_partition struct_ok={{b_struct}}",
      flush=True)
"""


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


@pytest.mark.slow
class TestTwoProcessRendezvous:
    def test_two_process_data_parallel_tree(self, tmp_path):
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        machines = f"127.0.0.1:{_free_port()},127.0.0.1:{_free_port()}"
        procs, outs = [], []
        for pid in range(2):
            outfile = str(tmp_path / f"rec_{pid}.npy")
            outs.append(outfile)
            src = _WORKER_SRC.format(root=root, machines=machines,
                                     outfile=outfile)
            env = dict(os.environ,
                       LIGHTGBM_TPU_PROCESS_ID=str(pid))
            # the workers pin their own backend; drop the parent's
            # virtual-device flags so they don't fight the pin
            env.pop("XLA_FLAGS", None)
            procs.append(subprocess.Popen(
                [sys.executable, "-c", src], env=env,
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                text=True))
        logs = []
        for p in procs:
            try:
                log, _ = p.communicate(timeout=600)
            except subprocess.TimeoutExpired:
                for q in procs:
                    q.kill()
                raise
            logs.append(log)
        for pid, (p, log) in enumerate(zip(procs, logs)):
            assert p.returncode == 0, f"rank {pid} failed:\n{log[-4000:]}"
        rec0 = np.load(outs[0])
        rec1 = np.load(outs[1])
        # both ranks must materialize IDENTICAL split records: the grower
        # output is replicated, so any divergence means the collective
        # ran inconsistently
        np.testing.assert_array_equal(rec0, rec1)
        assert rec0[:, 14].sum() >= 3
        # full lgb.train over the 2-process mesh: identical models
        m0 = open(outs[0] + ".model").read()
        m1 = open(outs[1] + ".model").read()
        assert m0 == m1 and "tree" in m0
        # distributed metrics over the partitioned valid set: both ranks
        # must report BITWISE-identical metric curves (same collective,
        # same arithmetic order) and stop at the same iteration...
        import json
        es0 = json.load(open(outs[0] + ".esjson"))
        es1 = json.load(open(outs[1] + ".esjson"))
        assert es0 == es1, "ranks diverged on metrics/early stopping"
        assert es0["best_iter"] == es1["best_iter"]
        # ...and the reported value must be the GLOBAL metric: the last
        # curve entry equals the numpy full-valid-set computation (f32
        # score-state accumulation vs the predictor's f64 sum bounds the
        # tolerance)
        assert es0["curve_ll"][-1] == pytest.approx(es0["expected_ll"],
                                                    abs=2e-4)
        assert es0["curve_auc"][-1] == pytest.approx(es0["expected_auc"],
                                                     abs=2e-4)
        # early stopping actually engaged (12 rounds max, patience 2)
        assert 1 <= es0["best_iter"] <= es0["n_iter"] <= 12
        # pre-partitioned TRAINING: identical models on both ranks, and
        # (deterministic f64, same global row order) bit-equal to the
        # serial full-data model; the distributed train-AUC is the
        # GLOBAL statistic so it matches the serial run's exactly
        pt0 = open(outs[0] + ".ptmodel").read()
        pt1 = open(outs[1] + ".ptmodel").read()
        assert pt0 == pt1 and "tree" in pt0
        ptj0 = json.load(open(outs[0] + ".ptjson"))
        ptj1 = json.load(open(outs[1] + ".ptjson"))
        assert ptj0 == ptj1
        # every split decision identical to serial full-data training;
        # value lines within the f32-downcast rounding band
        assert ptj0["struct_ok"], "partitioned splits diverged from serial"
        # value lines print 6-digit-rounded; one print digit = 1e-6
        assert ptj0["val_delta"] < 1e-5, ptj0
        assert ptj0["auc_pt"] == pytest.approx(ptj0["auc_sr"], abs=1e-6)
        assert ptj0["auc_pt"] > 0.9
        # sparse COO x pre_partition: both ranks identical, structurally
        # equal to serial-sparse full-data training
        spj0 = json.load(open(outs[0] + ".spjson"))
        spj1 = json.load(open(outs[1] + ".spjson"))
        assert spj0 == spj1
        assert spj0["struct_ok"], "sparse partitioned diverged from serial"
        assert spj0["val_delta"] < 1e-5, spj0
        assert "tree" in spj0["model"]
        # GOSS x pre_partition: per-machine sampling, identical global
        # model on both ranks
        g0 = open(outs[0] + ".gossmodel").read()
        g1 = open(outs[1] + ".gossmodel").read()
        assert g0 == g1 and "tree" in g0
        # lambdarank x pre_partition: local per-query lambdas, global
        # histograms and a globally-reduced NDCG — structural parity
        # with serial full-data and matching metric
        lr0 = json.load(open(outs[0] + ".lrjson"))
        lr1 = json.load(open(outs[1] + ".lrjson"))
        assert lr0 == lr1
        assert lr0["struct_ok"], "lambdarank partitioned diverged"
        assert lr0["ndcg_pt"] == pytest.approx(lr0["ndcg_sr"], abs=1e-6)
        # percentile-renew x pre_partition: bitwise rank agreement (the
        # leaf averaging is a collective) and the refit actually learns
        q0 = json.load(open(outs[0] + ".qjson"))
        q1 = json.load(open(outs[1] + ".qjson"))
        assert q0 == q1, "renew ranks diverged"
        assert "tree" in q0["model"]
        assert q0["l1"] < 0.7 * q0["base_l1"], q0  # 3 trees at lr 0.5
        # EFB x pre_partition: globally-agreed plan, identical ranks,
        # structural parity with the serial full-data plan
        e0 = json.load(open(outs[0] + ".efbjson"))
        e1 = json.load(open(outs[1] + ".efbjson"))
        assert e0 == e1, "EFB ranks diverged"
        assert e0["struct_ok"], "EFB partitioned diverged from serial"
        assert "tree" in e0["model"]

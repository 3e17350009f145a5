"""Stage-9: the complete ML loop (BASELINE config #5).

Records flow from a fan-out into the scheduler's record sink, the
announcer ships them to the trainer, the trainer fits the MLP on the
uploaded records (loss decreases), registers a versioned model with the
manager, the scheduler pulls it into the ``ml`` evaluator — and then makes
*different* parent choices than the rule-based default, preferring the
parent that historically delivered fast pieces.
"""

import asyncio
import json
import time

import numpy as np
import pytest

from dragonfly2_tpu.idl.messages import (Host, HostType, PieceInfo,
                                         PieceResult, PeerResult,
                                         TopologyInfo)
from dragonfly2_tpu.manager import Manager, ManagerConfig
from dragonfly2_tpu.scheduler import Scheduler, SchedulerConfig
from dragonfly2_tpu.scheduler.announcer import SchedulerAnnouncer
from dragonfly2_tpu.scheduler.evaluator import Evaluator
from dragonfly2_tpu.scheduler.evaluator_ml import (MLEvaluator,
                                                   parent_feature_row)
from dragonfly2_tpu.scheduler.records import DownloadRecords
from dragonfly2_tpu.scheduler.resource import PeerState
from dragonfly2_tpu.trainer import features, params_io, serving, training
from dragonfly2_tpu.trainer.server import Trainer, TrainerConfig

from conftest import run


# ---------------------------------------------------------------- units

class TestFeatures:
    def test_label_monotone_in_throughput(self):
        fast = features.label_from_cost(4 << 20, 4.0)      # ~1 GB/s
        mid = features.label_from_cost(4 << 20, 40.0)      # ~100 MB/s
        slow = features.label_from_cost(4 << 20, 4000.0)   # ~1 MB/s
        assert fast > mid > slow
        assert 0.0 < slow and fast <= 1.0

    def test_records_to_arrays_skips_unlabeled(self):
        rows = [{"features": [0.0] * features.FEATURE_DIM, "label": 0.5},
                {"kind": "peer"}]
        data = features.records_to_arrays(rows)
        assert data["x"].shape == (1, features.FEATURE_DIM)

    def test_topology_graph_padding(self):
        rows = [{"src": "a", "dst": "b", "avg_rtt_us": 50.0, "count": 3}]
        g = features.topology_to_graph(rows)
        assert g["edge_mask"].sum() == 1
        assert g["nodes"].shape[0] >= 2          # padded bucket


class TestParamsIO:
    def test_round_trip(self):
        tree = {"layers": [{"w": np.ones((3, 4), np.float32),
                            "b": np.zeros((4,), np.float32)}],
                "scalar": np.float32(2.5)}
        blob = params_io.serialize_params(tree, {"k": "v"})
        back, meta = params_io.deserialize_params(blob)
        assert meta == {"k": "v"}
        assert isinstance(back["layers"], list)
        np.testing.assert_array_equal(back["layers"][0]["w"],
                                      tree["layers"][0]["w"])

    def test_numpy_serving_matches_jax_forward(self):
        import jax

        from dragonfly2_tpu.trainer import models

        params = models.init_mlp(jax.random.PRNGKey(1))
        x = np.random.default_rng(0).uniform(
            size=(8, features.FEATURE_DIM)).astype(np.float32)
        jax_out = np.asarray(models.mlp_forward(params, x))
        host = jax.tree_util.tree_map(np.asarray, params)
        np_out = serving.mlp_forward_np(host, x)
        # bf16 matmul on the jax side vs f32 numpy: loose but honest bound
        np.testing.assert_allclose(jax_out, np_out, atol=0.15, rtol=0.15)


class TestTraining:
    def test_mlp_fits_synthetic_records(self):
        rng = np.random.default_rng(3)
        rows = []
        for _ in range(256):
            feats = rng.uniform(size=features.FEATURE_DIM)
            label = float(np.clip(feats[0] * 0.8 + 0.1, 0, 1))
            rows.append({"features": feats.tolist(), "label": label})
        fitted = training.train_mlp(rows, epochs=10, use_mesh=False)
        assert fitted is not None
        blob, metrics = fitted
        assert metrics["final_loss"] < metrics["first_epoch_loss"]
        infer = serving.make_mlp_infer(blob)
        hi = [1.0] + [0.5] * (features.FEATURE_DIM - 1)
        lo = [0.0] + [0.5] * (features.FEATURE_DIM - 1)
        assert infer([hi])[0] > infer([lo])[0]

    def test_too_few_rows_returns_none(self):
        assert training.train_mlp([], use_mesh=False) is None

    def test_odd_row_count_fits_over_the_mesh(self):
        """257 rows cannot tile dp=4 (conftest's 8 devices: dp 4 x tp 2):
        the batch rounds up to a multiple of dp and wraps around."""
        rng = np.random.default_rng(5)
        rows = [{"features": rng.uniform(size=features.FEATURE_DIM).tolist(),
                 "label": float(rng.uniform())} for _ in range(257)]
        blob, metrics = training.train_mlp(rows, epochs=3)
        assert metrics["mesh"] == {"dp": 4, "tp": 2}
        assert metrics["param_platforms"] == ["cpu"]
        assert np.isfinite(metrics["final_loss"])
        # where the fit ran is not part of the model: same rows and seed,
        # same blob, meshed or not (the meta's device count aside)
        _, meta = serving.params_io.deserialize_params(blob)
        assert "mesh" not in meta and "param_platforms" not in meta


# ---------------------------------------------------------------- e2e loop

def _host(hid, *, slice_name="slice-0", coords=(0, 0)):
    return Host(id=hid, ip="127.0.0.1", port=1, download_port=2,
                type=HostType.NORMAL,
                topology=TopologyInfo(slice_name=slice_name, worker_index=0,
                                      ici_coords=coords, num_chips=4,
                                      zone="z-a"))


def _simulate_fanout(scheduler, *, n_pieces=40):
    """Drive the resource model + record sink the way a real fan-out does:
    child c pulls from two parents — the same-slice (ICI) parent is SLOW,
    the cross-slice (DCN) parent is FAST. The rule-based evaluator prefers
    ICI; the learned model must discover the opposite."""
    svc = scheduler.service
    res = scheduler.resource
    task = res.get_or_create_task("t" * 64, "http://origin/blob")
    task.set_content_info(n_pieces * (4 << 20), 4 << 20, n_pieces)

    child_host = res.store_host(_host("h-child", coords=(0, 0)))
    ici_host = res.store_host(_host("h-ici", coords=(0, 1)))
    dcn_host = res.store_host(_host("h-dcn", slice_name="slice-1",
                                    coords=(3, 3)))

    child = res.get_or_create_peer("p-child" * 8, task, child_host)
    ici = res.get_or_create_peer("p-ici" * 8, task, ici_host)
    dcn = res.get_or_create_peer("p-dcn" * 8, task, dcn_host)
    for p in (child, ici, dcn):
        p.transit(PeerState.RUNNING)
    ici.finished_pieces.update(range(n_pieces))
    dcn.finished_pieces.update(range(n_pieces))

    records = svc.records
    for num in range(n_pieces):
        # ICI parent: stalls (~4 MB/s); DCN parent: ~800 MB/s
        for parent, cost in ((ici, 1000), (dcn, 5)):
            info = PieceInfo(piece_num=num, range_start=num * (4 << 20),
                             range_size=4 << 20, download_cost_ms=cost)
            records.on_piece(child, PieceResult(
                task_id=task.id, src_peer_id=child.id,
                dst_peer_id=parent.id, piece_info=info, success=True))
    records.on_peer(child, PeerResult(
        task_id=task.id, peer_id=child.id, success=True,
        content_length=task.content_length, total_piece_count=n_pieces,
        cost_ms=12000))
    return task, child, ici, dcn


def test_ml_loop_end_to_end(tmp_path):
    async def main():
        mgr = Manager(ManagerConfig(listen_ip="127.0.0.1", rest_port=0,
                                    grpc_port=0, db_path=str(tmp_path / "m.db")))
        await mgr.start()
        trainer = Trainer(TrainerConfig(
            listen_ip="127.0.0.1", data_dir=str(tmp_path / "spool"),
            manager_addresses=[f"127.0.0.1:{mgr.port}"], min_rows=32))
        await trainer.start()

        cfg = SchedulerConfig(listen_ip="127.0.0.1", algorithm="ml",
                              trainer_address=f"127.0.0.1:{trainer.port}",
                              records_dir=str(tmp_path / "records"))
        sched = Scheduler(cfg)
        await sched.start()
        # manager link normally comes from _attach_manager; wire directly
        from dragonfly2_tpu.rpc.manager_link import ManagerLink
        sched.manager = ManagerLink([f"127.0.0.1:{mgr.port}"])

        try:
            evaluator = sched.scheduling.evaluator
            assert isinstance(evaluator, MLEvaluator)
            assert evaluator.infer is None          # cold start

            task, child, ici, dcn = _simulate_fanout(sched)
            assert sched.service.records.piece_row_count() >= 64

            # rule-based ordering before the model lands: ICI parent wins
            base = Evaluator()
            total = task.total_piece_count
            assert base.evaluate(child, ici, total_piece_count=total) > \
                base.evaluate(child, dcn, total_piece_count=total)

            ann = sched.announcer or SchedulerAnnouncer(sched)
            assert await ann.upload_once()           # records -> trainer(+fit)
            assert trainer.service.latest, "trainer produced no model"
            _, metrics = trainer.service.latest[features.MLP_MODEL_NAME]
            assert metrics["final_loss"] < metrics["first_epoch_loss"]

            assert await ann.refresh_model_once()    # manager -> evaluator
            assert evaluator.infer is not None
            assert ann.model_version == metrics["version"]

            # the learned evaluator flips the choice: fast DCN beats slow ICI
            row_ici = parent_feature_row(child, ici, total_piece_count=total)
            row_dcn = parent_feature_row(child, dcn, total_piece_count=total)
            s_ici, s_dcn = evaluator.infer([row_ici, row_dcn])
            assert s_dcn > s_ici, (s_dcn, s_ici)
            assert evaluator.evaluate(child, dcn, total_piece_count=total) > \
                evaluator.evaluate(child, ici, total_piece_count=total)

            # parity surface: trainer-side inference serves the same model
            from dragonfly2_tpu.idl.messages import ModelInferRequest
            resp = await trainer.service.model_infer(
                ModelInferRequest(features=[row_dcn, row_ici]), None)
            assert resp.outputs[0] > resp.outputs[1]
            assert resp.model_version == metrics["version"]

            # registry is queryable over REST
            import aiohttp
            async with aiohttp.ClientSession() as http:
                async with http.get(
                        f"http://127.0.0.1:{mgr.rest.port}/api/v1/models"
                ) as r:
                    models_list = await r.json()
            assert any(m["name"] == features.MLP_MODEL_NAME
                       for m in models_list)
        finally:
            await sched.stop()
            await trainer.stop()
            await mgr.stop()

    run(main())


def test_records_requeue_on_trainer_outage(tmp_path):
    async def main():
        cfg = SchedulerConfig(listen_ip="127.0.0.1", algorithm="ml",
                              trainer_address="127.0.0.1:1")   # nothing there
        sched = Scheduler(cfg, records=DownloadRecords())
        await sched.start()
        try:
            _simulate_fanout(sched, n_pieces=8)
            before = sched.service.records.piece_row_count()
            assert before > 0
            ann = SchedulerAnnouncer(sched)
            with pytest.raises(Exception):
                await ann.upload_once()
            # rows survived the failed upload
            assert sched.service.records.piece_row_count() == before
            await ann.stop()
        finally:
            await sched.stop()

    run(main())


# ------------------------------------------------- decision-outcome folds

def _decision_row(did, *, v1=False, cands=("pa", "pb"), locality=(0.9, 0.4)):
    """A ledger decision row. ``v1=True`` drops the federation metadata
    (no ``link_tier`` on candidates, no ``federation`` block) — the exact
    shape pre-federation schedulers logged and BENCH_pr8 committed."""
    row = {"kind": "decision", "decision_id": did, "decision_kind": "find",
           "task_id": "t1", "peer_id": "c1", "host_id": "h-c1",
           "candidates": [], "chosen": [cands[0]]}
    for i, p in enumerate(cands):
        cand = {"peer_id": p, "host_id": f"h-{p}", "rank": i + 1,
                "total": 0.9 - 0.1 * i,
                "features": [1.0, 1.0, 1.0, 0.5, locality[i], 4.0, 0.0]}
        if not v1:
            cand["link_tier"] = "ici" if i == 0 else "dcn"
        row["candidates"].append(cand)
    if not v1:
        row["federation"] = {"pod": "pod-a"}
    return row


def _piece_row(did, parent, label):
    return {"kind": "piece", "task_id": "t1", "peer_id": "c1",
            "decision_id": did, "parent_peer_id": parent,
            "piece_length": 4 << 20, "cost_ms": 10.0, "label": label}


class TestDecisionOutcomeRows:
    """Satellite: v1 and v2 record rows MIX in one training snapshot — a
    fleet mid-upgrade uploads both, and the fold must parse either
    without crashing the trainer."""

    def test_v2_rows_fold_with_federation_metadata(self):
        rows = [_decision_row("d1"),
                _piece_row("d1", "pa", 0.8), _piece_row("d1", "pa", 0.6)]
        folds = features.decision_outcome_rows(rows)
        assert len(folds) == 1
        f = folds[0]
        assert f["parent_peer_id"] == "pa"
        assert f["label"] == pytest.approx(0.7)     # mean over pieces
        assert f["pieces"] == 2 and f["rank"] == 1
        assert f["link_tier"] == "ici" and f["pod"] == "pod-a"

    def test_v1_rows_parse_with_defaults(self):
        rows = [_decision_row("d1", v1=True), _piece_row("d1", "pb", 0.5)]
        folds = features.decision_outcome_rows(rows)
        assert len(folds) == 1
        assert folds[0]["link_tier"] == "" and folds[0]["pod"] == ""

    def test_mixed_fleet_upgrade_trains(self):
        """The teeth: a v1+v2 mixed snapshot folds cleanly AND fits —
        mid-upgrade the trainer must keep producing models, not crash on
        the first old-schema row."""
        from dragonfly2_tpu.trainer import pipeline
        rows = []
        for i in range(6):
            v1 = i % 2 == 1
            did = f"d{i}"
            rows.append(_decision_row(did, v1=v1))
            rows.append(_piece_row(did, "pa", 0.9 - 0.02 * i))
            rows.append(_piece_row(did, "pb", 0.3 + 0.02 * i))
        folds = features.decision_outcome_rows(rows)
        assert len(folds) == 12               # 6 decisions x 2 parents
        assert {f["pod"] for f in folds} == {"", "pod-a"}
        fitted = pipeline.train_decision_model(rows, seed=1, epochs=10,
                                               use_mesh=False)
        assert fitted is not None
        assert fitted[1]["supervision"] == "decision_outcomes"
        assert fitted[1]["rows"] == 12

    def test_wrong_feature_dim_fold_skipped(self):
        d = _decision_row("d1")
        d["candidates"][0]["features"] = [1.0, 2.0]       # stale layout
        rows = [d, _piece_row("d1", "pa", 0.8),
                _piece_row("d1", "pb", 0.4)]
        folds = features.decision_outcome_rows(rows)
        assert [f["parent_peer_id"] for f in folds] == ["pb"]


class TestPipeline:
    """Satellite: the offline pipeline — scheduler records JSONL in,
    versioned deterministic blob out."""

    def _rows(self, n=8):
        rows = []
        for i in range(n):
            did = f"d{i}"
            rows.append(_decision_row(did, v1=i % 2 == 1))
            rows.append(_piece_row(did, "pa", 0.85 - 0.01 * i))
            rows.append(_piece_row(did, "pb", 0.35 + 0.01 * i))
        return rows

    def test_records_dir_rotated_half_first_and_torn_tail(self, tmp_path):
        from dragonfly2_tpu.trainer import pipeline
        d = tmp_path / "records"
        d.mkdir()
        (d / "download.jsonl.1").write_text(
            json.dumps(_decision_row("d1")) + "\n")
        (d / "download.jsonl").write_text(
            json.dumps(_piece_row("d1", "pa", 0.7)) + "\n"
            + '{"kind": "piece", "torn')          # live-file torn tail
        rows = pipeline.load_records_jsonl(str(d))
        assert [r["kind"] for r in rows] == ["decision", "piece"]

    def test_seeded_fit_is_byte_deterministic(self):
        from dragonfly2_tpu.trainer import pipeline
        rows = self._rows()
        a = pipeline.train_decision_model(rows, seed=3, epochs=12,
                                          use_mesh=False)
        b = pipeline.train_decision_model(rows, seed=3, epochs=12,
                                          use_mesh=False)
        assert a is not None and b is not None
        # the rollout-dedupe contract: same rows + same seed -> same
        # BYTES -> same version hash; wall clock must not leak into blob
        assert a[0] == b[0]
        assert a[1]["version"] == b[1]["version"]
        c = pipeline.train_decision_model(rows, seed=4, epochs=12,
                                          use_mesh=False)
        assert c is not None and c[1]["version"] != a[1]["version"]

    def test_supervision_falls_back_to_piece_rows(self):
        from dragonfly2_tpu.trainer import pipeline
        rows = [{"features": [0.1 * i] + [0.5] * (features.FEATURE_DIM - 1),
                 "label": 0.1 + 0.08 * i} for i in range(10)]
        fitted = pipeline.train_decision_model(rows, seed=0, epochs=5,
                                               use_mesh=False)
        assert fitted is not None
        assert fitted[1]["supervision"] == "piece_rows"

    def test_cli_fit_writes_servable_blob(self, tmp_path, capsys):
        from dragonfly2_tpu.trainer import pipeline
        rec = tmp_path / "download.jsonl"
        rec.write_text("\n".join(json.dumps(r) for r in self._rows()))
        out = tmp_path / "mlp.npz"
        rc = pipeline.main(["--records", str(rec), "--out", str(out),
                            "--epochs", "10", "--json"])
        assert rc == 0
        metrics = json.loads(capsys.readouterr().out)
        assert metrics["supervision"] == "decision_outcomes"
        infer = serving.make_mlp_infer(out.read_bytes())
        assert infer.version == metrics["version"]

    def test_cli_missing_records_is_exit_1(self, capsys):
        from dragonfly2_tpu.trainer import pipeline
        assert pipeline.main(["--records", "/nonexistent/x.jsonl"]) == 1
        assert "pipeline:" in capsys.readouterr().err


class TestGNNImputation:
    """VERDICT r4 #7: the trained topology GNN must be SERVED — unprobed
    pairs get imputed RTTs in the TopologyStore and the nt evaluator's
    schedule changes because of it."""

    @staticmethod
    def _fit_gnn():
        # synthetic pod, two slices {a,b,e} and {c,d,f}: intra-slice links
        # fast, cross-slice slow. The pairs (hb,he) [intra] and (hb,hc)
        # [cross] are deliberately NEVER observed — the GNN must place the
        # hosts from the observed structure and discriminate the two.
        rows = []
        fast = [("ha", "hb"), ("ha", "he"), ("hc", "hd"), ("hc", "hf"),
                ("hd", "hf")]
        slow = [("ha", "hc"), ("ha", "hd"), ("he", "hd"), ("he", "hf"),
                ("hb", "hf"), ("ha", "hf"), ("he", "hc")]
        for s, d in fast:
            rows.append({"src": s, "dst": d, "avg_rtt_us": 30.0, "count": 5})
        for s, d in slow:
            rows.append({"src": s, "dst": d, "avg_rtt_us": 8000.0, "count": 5})
        fitted = training.train_gnn(rows, epochs=150, use_mesh=False)
        assert fitted is not None
        return rows, fitted[0]

    def test_unprobed_pair_gets_imputed_rtt(self):
        from dragonfly2_tpu.scheduler.topology_store import TopologyStore

        rows, blob = self._fit_gnn()
        store = TopologyStore()
        for r in rows:
            for _ in range(2):
                store.record(r["src"], r["dst"], int(r["avg_rtt_us"]))
        # hb-hc was NEVER probed
        assert store.avg_rtt_us("hb", "hc") is None
        store.bind_imputer(serving.make_gnn_impute(blob))
        imputed = store.avg_rtt_us("hb", "hc")
        assert imputed is not None and imputed > 0
        # measured pairs stay measured
        assert abs(store.avg_rtt_us("ha", "hb") - 30.0) < 1.0
        # DISCRIMINATION, not a constant: the never-observed intra-slice
        # pair must impute meaningfully faster than the never-observed
        # cross-slice pair (a label-leaking or collapsed model scores both
        # the same)
        intra = store.avg_rtt_us("hb", "he")
        cross = store.avg_rtt_us("hb", "hc")
        assert intra is not None and cross is not None
        assert intra * 1.5 < cross, (intra, cross)

    def test_imputation_changes_nt_schedule(self):
        from dragonfly2_tpu.scheduler.evaluator import make_evaluator
        from dragonfly2_tpu.scheduler.topology_store import TopologyStore

        rows, blob = self._fit_gnn()
        store = TopologyStore()
        for r in rows:
            store.record(r["src"], r["dst"], int(r["avg_rtt_us"]))
        ev = make_evaluator("nt", topo_store=store)

        class H:   # minimal host/peer stand-ins for _locality_score
            def __init__(self, hid):
                self.id = hid
                self.msg = type("M", (), {"topology": None})()

        class P:
            def __init__(self, hid):
                self.host = H(hid)

        before = ev._locality_score(P("hb"), P("hc"))
        store.bind_imputer(serving.make_gnn_impute(blob))
        after = ev._locality_score(P("hb"), P("hc"))
        # unprobed pair: static fallback before, imputed RTT after
        assert after != before

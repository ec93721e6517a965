"""HTTP server tests: endpoint surface + remote-write -> query loop."""

import pyarrow as pa
import pytest
from aiohttp.test_utils import TestClient, TestServer

from horaedb_tpu.common.error import HoraeError
from horaedb_tpu.server.config import Config
from horaedb_tpu.server.main import build_app, snappy_decompress
from tests.conftest import async_test
from tests.test_engine import make_remote_write


def make_config(tmp_path) -> Config:
    return Config.from_toml(
        f"""
port = 0
[test]
segment_duration = "2h"
[metric_engine.storage.object_store]
type = "Local"
data_dir = "{tmp_path}/data"
"""
    )


async def make_client(tmp_path) -> TestClient:
    app = await build_app(make_config(tmp_path))
    client = TestClient(TestServer(app))
    await client.start_server()
    return client


class TestSplitEndpoint:
    @async_test
    async def test_split_region_endpoint(self, tmp_path):
        """POST /admin/split_region halves a region; writes before and after
        the split all remain queryable (fan-out merge)."""
        cfg = Config.from_toml(
            f"""
port = 0
[test]
segment_duration = "2h"
[metric_engine]
num_regions = 2
[metric_engine.storage.object_store]
type = "Local"
data_dir = "{tmp_path}/data"
"""
        )
        app = await build_app(cfg)
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            hosts1 = [f"h{i:02d}" for i in range(10)]
            payload = make_remote_write([
                ({"__name__": "splitm", "host": h}, [(1000, 1.0)])
                for h in hosts1
            ])
            r = await client.post("/api/v1/write", data=payload)
            assert r.status == 200
            r = await client.post("/admin/split_region?region=0")
            body = await r.json()
            assert r.status == 200 and body["daughter"] == 2, body
            assert body["regions"] == [0, 1, 2]
            hosts2 = [f"g{i:02d}" for i in range(10)]
            payload2 = make_remote_write([
                ({"__name__": "splitm", "host": h}, [(2000, 2.0)])
                for h in hosts2
            ])
            r = await client.post("/api/v1/write", data=payload2)
            assert r.status == 200
            r = await client.post(
                "/api/v1/query",
                json={"metric": "splitm", "start_ms": 0, "end_ms": 10_000},
            )
            body = await r.json()
            assert r.status == 200 and body["rows"] == 20, body
            # bad requests fail cleanly
            r = await client.post("/admin/split_region?region=99")
            assert r.status == 400
            r = await client.post("/admin/split_region")
            assert r.status == 400
        finally:
            await client.close()

    @async_test
    async def test_split_rejected_on_unregioned_deployment(self, tmp_path):
        client = await make_client(tmp_path)
        try:
            r = await client.post("/admin/split_region?region=0")
            assert r.status == 400
            assert "not a regioned" in (await r.json())["error"]
        finally:
            await client.close()


class TestConfigParsing:
    def test_defaults(self):
        c = Config.from_dict(None)
        assert c.port == 5000
        assert c.test.write_worker_num == 1
        assert c.metric_engine.storage.object_store.type == "Local"

    def test_example_toml_parses(self):
        with open("docs/example.toml") as f:
            c = Config.from_toml(f.read())
        assert c.port == 5000
        assert c.test.segment_duration.as_millis() == 12 * 3600_000
        assert (
            c.metric_engine.storage.time_merge_storage.scheduler.memory_limit.as_bytes()
            == 2 * 1024**3
        )

    def test_unknown_key_rejected(self):
        """deny_unknown_fields semantics (config.rs serde attribute)."""
        with pytest.raises(HoraeError, match="unknown config keys"):
            Config.from_toml("port = 1\nwhatever = 2\n")
        with pytest.raises(HoraeError, match="unknown config keys"):
            Config.from_toml("[test]\nnope = 1\n")

    def test_s3like_accepted_unknown_type_rejected(self):
        """Divergence from the reference (main.rs:112 panics 'S3 not support
        yet'): S3Like validates and boots here — see tests/test_objstore_s3.py
        for the full engine-on-S3 loop. Unrecognized tags still fail loudly."""
        c = Config.from_toml(
            '[metric_engine.storage.object_store]\ntype = "S3Like"\n'
            'endpoint = "http://127.0.0.1:9000"\nbucket = "b"\n'
        )
        c.validate()
        with pytest.raises(HoraeError, match="unknown object_store type"):
            Config.from_toml(
                '[metric_engine.storage.object_store]\ntype = "S3"\n'
            ).validate()


class TestEndpoints:
    @async_test
    async def test_root_toggle_compact_metrics(self, tmp_path):
        client = await make_client(tmp_path)
        try:
            r = await client.get("/")
            assert r.status == 200
            assert (await r.json())["status"] == "ok"

            r = await client.get("/toggle")
            assert (await r.json())["enable_write"] is True
            r = await client.get("/toggle")
            assert (await r.json())["enable_write"] is False

            r = await client.get("/compact")
            assert r.status == 200

            r = await client.get("/metrics")
            text = await r.text()
            assert "horaedb_uptime_seconds" in text
            assert "horaedb_parser_pool_size" in text
            assert 'horaedb_ssts_live{table="data"}' in text
            assert 'horaedb_manifest_deltas{table="series"}' in text
            assert "horaedb_ingest_buffered_rows" in text
        finally:
            await client.close()

    @async_test
    async def test_remote_write_then_query(self, tmp_path):
        client = await make_client(tmp_path)
        try:
            payload = make_remote_write(
                [
                    ({"__name__": "cpu", "host": "a"}, [(1000, 1.5), (2000, 2.5)]),
                    ({"__name__": "cpu", "host": "b"}, [(1500, 7.0)]),
                ]
            )
            r = await client.post("/api/v1/write", data=payload)
            assert r.status == 200
            assert (await r.json())["samples"] == 3

            r = await client.post(
                "/api/v1/query",
                json={"metric": "cpu", "start_ms": 0, "end_ms": 10_000},
            )
            body = await r.json()
            assert body["rows"] == 3
            assert sorted(body["value"]) == [1.5, 2.5, 7.0]

            # filtered query
            r = await client.post(
                "/api/v1/query",
                json={
                    "metric": "cpu",
                    "start_ms": 0,
                    "end_ms": 10_000,
                    "filters": {"host": "a"},
                },
            )
            body = await r.json()
            assert body["rows"] == 2

            # downsample query
            r = await client.post(
                "/api/v1/query",
                json={"metric": "cpu", "start_ms": 0, "end_ms": 4000, "bucket_ms": 2000},
            )
            body = await r.json()
            assert body["buckets"] == 2
            assert len(body["tsids"]) == 2

            # labels
            r = await client.get("/api/v1/labels?metric=cpu&key=host")
            assert (await r.json())["values"] == ["a", "b"]

            # metric + series listings
            r = await client.get("/api/v1/metrics")
            assert (await r.json())["metrics"] == ["cpu"]
            r = await client.get("/api/v1/series?metric=cpu")
            series = (await r.json())["series"]
            assert sorted(s["host"] for s in series) == ["a", "b"]
            assert all("__tsid__" in s for s in series)

            # raw-query row limit
            r = await client.post(
                "/api/v1/query",
                json={"metric": "cpu", "start_ms": 0, "end_ms": 10_000, "limit": 2},
            )
            body = await r.json()
            assert body["rows"] == 2 and body["truncated"] is True
        finally:
            await client.close()

    @async_test
    async def test_exemplars_roundtrip(self, tmp_path):
        from horaedb_tpu.pb import remote_write_pb2

        client = await make_client(tmp_path)
        try:
            req = remote_write_pb2.WriteRequest()
            ts = req.timeseries.add()
            for k, v in ((b"__name__", b"lat"), (b"host", b"a")):
                lab = ts.labels.add(); lab.name = k; lab.value = v
            s = ts.samples.add(); s.timestamp = 1000; s.value = 0.5
            ex = ts.exemplars.add(); ex.value = 0.93; ex.timestamp = 1200
            lab = ex.labels.add(); lab.name = b"trace_id"; lab.value = b"t-42"
            r = await client.post("/api/v1/write", data=req.SerializeToString())
            assert r.status == 200

            r = await client.post(
                "/api/v1/query",
                json={"metric": "lat", "start_ms": 0, "end_ms": 10_000,
                      "exemplars": True},
            )
            body = await r.json()
            assert body["rows"] == 1
            assert body["value"] == [0.93]
            assert body["labels"] == [{"trace_id": "t-42"}]
        finally:
            await client.close()

    @async_test
    async def test_remote_write_snappy(self, tmp_path):
        client = await make_client(tmp_path)
        try:
            payload = make_remote_write([({"__name__": "m", "h": "x"}, [(1000, 1.0)])])
            comp = bytes(pa.Codec("snappy").compress(payload))
            assert snappy_decompress(comp) == payload
            r = await client.post(
                "/api/v1/write", data=comp, headers={"Content-Encoding": "snappy"}
            )
            assert r.status == 200
            assert (await r.json())["samples"] == 1
        finally:
            await client.close()

    @async_test
    async def test_bad_requests(self, tmp_path):
        client = await make_client(tmp_path)
        try:
            ok_payload = make_remote_write([({"__name__": "cpu", "h": "x"}, [(1000, 1.0)])])
            await client.post("/api/v1/write", data=ok_payload)
            r = await client.post(
                "/api/v1/write", data=b"\xff\xfe", headers={"Content-Encoding": "snappy"}
            )
            assert r.status == 400
            r = await client.post("/api/v1/query", json={"metric": "x"})  # missing fields
            assert r.status == 400
            r = await client.post(
                "/api/v1/query", json={"metric": "nope", "start_ms": 0, "end_ms": 1}
            )
            assert (await r.json())["series"] == []
            # absurd resolution (billions of buckets) must 400, not hang
            r = await client.post(
                "/api/v1/query",
                json={"metric": "cpu", "start_ms": 0,
                      "end_ms": 1_700_000_000_000, "bucket_ms": 1000},
            )
            assert r.status == 400
            assert "resolution" in (await r.json())["error"]
        finally:
            await client.close()


class TestRegionedServer:
    @async_test
    async def test_regioned_write_query_metrics(self, tmp_path):
        """num_regions > 1: the full HTTP surface works over the region
        router (write splits, queries route, /metrics shows per-region
        tables)."""
        from horaedb_tpu.server.config import Config
        from horaedb_tpu.server.main import build_app
        from aiohttp.test_utils import TestClient, TestServer

        cfg = Config.from_dict({
            "metric_engine": {
                "num_regions": 3,
                "storage": {"object_store": {"type": "Local",
                                             "data_dir": str(tmp_path)}},
            }
        })
        app = await build_app(cfg)
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            payload = make_remote_write(
                [
                    ({"__name__": f"m{i}", "host": "a"}, [(1000, float(i))])
                    for i in range(8)
                ]
            )
            r = await client.post("/api/v1/write", data=payload)
            assert r.status == 200 and (await r.json())["samples"] == 8
            for i in range(8):
                r = await client.post(
                    "/api/v1/query",
                    json={"metric": f"m{i}", "start_ms": 0, "end_ms": 10_000},
                )
                body = await r.json()
                assert r.status == 200 and body["rows"] == 1, body
            r = await client.get("/api/v1/metrics")
            assert (await r.json())["metrics"] == [f"m{i}" for i in range(8)]
            r = await client.get("/metrics")
            text = await r.text()
            assert 'horaedb_ssts_live{table="region-0/data"}' in text
            assert 'horaedb_ssts_live{table="region-2/data"}' in text
        finally:
            await client.close()


class TestGetQuery:
    @async_test
    async def test_get_query_with_filters(self, tmp_path):
        """GET /api/v1/query: scalar params in the query string, leftover
        keys are tag filters."""
        client = await make_client(tmp_path)
        try:
            payload = make_remote_write(
                [
                    ({"__name__": "cpu", "host": "a"}, [(1000, 1.0), (2000, 2.0)]),
                    ({"__name__": "cpu", "host": "b"}, [(1500, 7.0)]),
                ]
            )
            r = await client.post("/api/v1/write", data=payload)
            assert r.status == 200
            r = await client.get(
                "/api/v1/query?metric=cpu&start_ms=0&end_ms=10000&host=a"
            )
            body = await r.json()
            assert r.status == 200 and body["rows"] == 2, body
            r = await client.get(
                "/api/v1/query?metric=cpu&start_ms=0&end_ms=10000&bucket_ms=2000&limit=5"
            )
            body = await r.json()
            assert r.status == 200 and body["buckets"] == 5 and len(body["tsids"]) == 2
            r = await client.get("/api/v1/query?metric=cpu")  # missing range
            assert r.status == 400
        finally:
            await client.close()

    @async_test
    async def test_get_query_rejections(self, tmp_path):
        client = await make_client(tmp_path)
        try:
            payload = make_remote_write([({"__name__": "cpu", "host": "a"}, [(1000, 1.0)])])
            await client.post("/api/v1/write", data=payload)
            # bucket_ms=0 must be a 400, not a ZeroDivisionError 500
            r = await client.get(
                "/api/v1/query?metric=cpu&start_ms=0&end_ms=10000&bucket_ms=0"
            )
            assert r.status == 400
            r = await client.post(
                "/api/v1/query",
                json={"metric": "cpu", "start_ms": 0, "end_ms": 1000, "bucket_ms": 0},
            )
            assert r.status == 400
            # duplicated tag key: loud 400, not a silently dropped filter
            r = await client.get(
                "/api/v1/query?metric=cpu&start_ms=0&end_ms=10000&host=a&host=b"
            )
            assert r.status == 400
            # falsy exemplar spellings stay sample queries
            r = await client.get(
                "/api/v1/query?metric=cpu&start_ms=0&end_ms=10000&exemplars=False"
            )
            body = await r.json()
            assert r.status == 200 and body["rows"] == 1
        finally:
            await client.close()


class TestObservability:
    @async_test
    async def test_trace_header_and_debug_roundtrip(self, tmp_path):
        """A query response echoes X-Horaedb-Trace-Id and
        GET /debug/traces/{id} returns that trace's span tree; /metrics
        grows the per-stage scan histogram after the query and the whole
        body passes the Prometheus text-format validator."""
        import sys
        from pathlib import Path

        sys.path.insert(
            0, str(Path(__file__).resolve().parent.parent / "tools")
        )
        import promcheck

        from horaedb_tpu.common import tracing

        tracing.configure(sample=1.0)
        client = await make_client(tmp_path)
        try:
            payload = make_remote_write(
                [({"__name__": "cpu", "host": "a"}, [(1000, 1.0), (2000, 2.0)])]
            )
            r = await client.post("/api/v1/write", data=payload)
            assert r.status == 200
            assert "X-Horaedb-Trace-Id" in r.headers

            r = await client.post(
                "/api/v1/query",
                json={"metric": "cpu", "start_ms": 0, "end_ms": 10_000},
            )
            assert r.status == 200
            trace_id = r.headers.get("X-Horaedb-Trace-Id")
            assert trace_id

            r = await client.get(f"/debug/traces/{trace_id}")
            assert r.status == 200
            tree = await r.json()
            assert tree["trace_id"] == trace_id
            assert tree["root"]["name"] == "POST /api/v1/query"
            assert tree["root"]["duration_s"] is not None

            r = await client.get("/debug/traces")
            body = await r.json()
            assert any(t["trace_id"] == trace_id for t in body["traces"])

            r = await client.get("/debug/traces/nope")
            assert r.status == 404

            r = await client.get("/metrics")
            text = await r.text()
            assert "horaedb_scan_stage_seconds_bucket" in text
            # the raw query actually drove the io_decode lane
            io_lines = [
                ln for ln in text.splitlines()
                if ln.startswith("horaedb_scan_stage_seconds_count"
                                 '{stage="io_decode"}')
            ]
            assert io_lines and float(io_lines[0].split()[-1]) > 0, io_lines
            assert "# TYPE horaedb_http_request_seconds histogram" in text
            assert "horaedb_storage_write_seconds_bucket" in text
            errors = promcheck.validate(text)
            assert not errors, errors[:10]
        finally:
            await client.close()

    @async_test
    async def test_sampling_disabled_no_header(self, tmp_path):
        from horaedb_tpu.common import tracing

        cfg = Config.from_toml(
            f"""
port = 0
[tracing]
sample = 0.0
[metric_engine.storage.object_store]
type = "Local"
data_dir = "{tmp_path}/data"
"""
        )
        app = await build_app(cfg)
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            r = await client.get("/")
            assert r.status == 200
            assert "X-Horaedb-Trace-Id" not in r.headers
        finally:
            await client.close()
            tracing.configure(sample=1.0)

    def test_env_knobs_seed_config_defaults(self, monkeypatch):
        """HORAEDB_TRACE_* must stay live when the config file has no
        [tracing] section: build_app applies the config, and a compiled
        default of 1.0 would clobber an operator's env override."""
        monkeypatch.setenv("HORAEDB_TRACE_SAMPLE", "0.25")
        monkeypatch.setenv("HORAEDB_TRACE_SLOW_S", "2.5")
        c = Config.from_toml("port = 1\n")
        assert c.tracing.sample == 0.25
        assert c.tracing.slow_threshold.as_millis() == 2500
        # explicit config wins over env
        c = Config.from_toml("[tracing]\nsample = 0.5\n")
        assert c.tracing.sample == 0.5

    def test_tracing_config_validates(self):
        with pytest.raises(HoraeError, match="tracing.sample"):
            Config.from_toml("[tracing]\nsample = 1.5\n").validate()
        c = Config.from_toml(
            '[tracing]\nsample = 0.25\nslow_threshold = "250ms"\n'
            "ring_capacity = 16\n"
        )
        c.validate()
        assert c.tracing.slow_threshold.as_millis() == 250

    @async_test
    async def test_debug_traces_limit_and_min_ms(self, tmp_path):
        """?limit= bounds the ring dump; ?min_ms= filters to slow traces
        only — together the 'last N slow traces' operator pull."""
        from horaedb_tpu.common import tracing

        tracing.configure(sample=1.0)
        client = await make_client(tmp_path)
        try:
            for _ in range(5):
                r = await client.get("/api/v1/metrics")
                assert r.status == 200
            r = await client.get("/debug/traces?limit=2")
            body = await r.json()
            assert len(body["traces"]) == 2
            # every real trace here is far under 10 minutes
            r = await client.get("/debug/traces?min_ms=600000")
            body = await r.json()
            assert body["traces"] == []
            # threshold 0 keeps everything (same as no filter)
            r = await client.get("/debug/traces?min_ms=0&limit=3")
            body = await r.json()
            assert len(body["traces"]) == 3
            r = await client.get("/debug/traces?min_ms=abc")
            assert r.status == 400
            r = await client.get("/debug/traces?limit=abc")
            assert r.status == 400
        finally:
            await client.close()


# the pinned EXPLAIN plan schema: every key a dashboard / the flight
# recorder may rely on (values vary per run; the SHAPE must not)
EXPLAIN_KEYS = {
    "mode", "regions", "ssts", "scan_paths", "agg_impl", "agg_impls",
    "stages_s", "lanes_s", "bound", "compile_s", "steady_s", "counts",
    "kernels", "tombstones_applied", "tombstone_rows_masked", "admission",
    "encoding", "serving", "cluster", "memory",
}
EXPLAIN_LANES = {"io", "host", "transfer", "kernel", "compile", "decode"}
# compressed-domain scan provenance (storage/encoding.py + ops/decode.py)
EXPLAIN_ENCODING_KEYS = {
    "lanes", "ssts_encoded", "encoded_bytes", "decoded_bytes",
    "pages_pruned", "runs_skipped", "decode_impls",
}
# serving-tier verdict (horaedb_tpu/serving): result-cache outcome,
# rollup substitution
EXPLAIN_SERVING_KEYS = {
    "cache", "rollup", "rollup_resolutions", "rollup_segments",
    "rollup_rows_read", "raw_segments",
}


class TestExplain:
    @async_test
    async def test_explain_schema_native_and_promql(self, tmp_path):
        """?explain=1 returns the pinned plan schema on the native raw +
        downsample forms and the PromQL instant + range forms; without
        the flag no explain key appears."""
        client = await make_client(tmp_path)
        try:
            payload = make_remote_write(
                [({"__name__": "exq", "host": h}, [(1000, 1.0), (2000, 2.0)])
                 for h in ("a", "b")]
            )
            r = await client.post("/api/v1/write", data=payload)
            assert r.status == 200

            def check_plan(plan, mode):
                assert plan is not None, "explain missing"
                assert EXPLAIN_KEYS <= set(plan), sorted(plan)
                assert plan["mode"] == mode
                assert EXPLAIN_LANES <= set(plan["lanes_s"])
                assert set(plan["ssts"]) == {"selected", "read",
                                             "bloom_pruned",
                                             "retention_pruned",
                                             "unavailable",
                                             "footer_lanes",
                                             "footer_walks"}
                assert isinstance(plan["compile_s"], (int, float))
                assert isinstance(plan["steady_s"], (int, float))
                assert plan["regions"] >= 1
                for k in plan["kernels"]:
                    assert {"kernel", "compiles", "calls"} <= set(k)
                # admission verdict (server/admission.py) rides every
                # admitted query's plan
                adm = plan["admission"]
                assert adm is not None and adm["admitted"] is True
                assert {"tenant", "queued", "queue_wait_s",
                        "cost_estimate_s", "inflight"} <= set(adm)
                assert adm["tenant"] == "default"
                # compressed-domain scan provenance rides every plan
                # (zeros/empty when the tree holds no encoded SSTs)
                encp = plan["encoding"]
                assert EXPLAIN_ENCODING_KEYS <= set(encp), sorted(encp)
                assert isinstance(encp["lanes"], dict)
                assert isinstance(encp["decode_impls"], list)
                # serving verdict rides every plan: this query reached the
                # choke point with serving ON, so the outcome is hit|miss
                srv = plan["serving"]
                assert EXPLAIN_SERVING_KEYS <= set(srv), sorted(srv)
                assert srv["cache"] in ("hit", "miss")
                assert srv["rollup"] in ("none", "1m", "1h", "mixed")
                # memory verdict (common/memtrace.py) rides every plan
                # with the pinned schema; default mode has the ledger on
                from horaedb_tpu.common import memtrace

                mem = plan["memory"]
                assert set(memtrace.VERDICT_KEYS) <= set(mem), sorted(mem)
                assert mem["enabled"] is True
                assert mem["deep"] is False
                assert isinstance(mem["per_stage"], dict)

            # native raw
            r = await client.post(
                "/api/v1/query?explain=1",
                json={"metric": "exq", "start_ms": 0, "end_ms": 10_000},
            )
            body = await r.json()
            assert r.status == 200 and body["rows"] == 4, body
            check_plan(body.get("explain"), "raw")
            assert body["explain"]["ssts"]["selected"] >= 1
            assert body["explain"]["bound"] is not None

            # native downsample: the plan names the dispatcher impl
            r = await client.post(
                "/api/v1/query?explain=1",
                json={"metric": "exq", "start_ms": 0, "end_ms": 4000,
                      "bucket_ms": 2000},
            )
            body = await r.json()
            assert r.status == 200, body
            check_plan(body.get("explain"), "downsample")
            assert body["explain"]["agg_impl"], body["explain"]

            # GET form: explain must act as a flag, NOT leak into filters
            r = await client.get(
                "/api/v1/query?metric=exq&start_ms=0&end_ms=10000&explain=1"
            )
            body = await r.json()
            assert r.status == 200 and body["rows"] == 4, body
            check_plan(body.get("explain"), "raw")

            # PromQL instant
            r = await client.get(
                "/api/v1/query?query=exq&time=2&explain=1"
            )
            body = await r.json()
            assert r.status == 200 and body["status"] == "success", body
            check_plan(body.get("explain"), "promql_instant")

            # PromQL range
            r = await client.get(
                "/api/v1/query_range?query=sum_over_time(exq[1s])"
                "&start=0&end=4&step=1&explain=1"
            )
            body = await r.json()
            assert r.status == 200 and body["status"] == "success", body
            check_plan(body.get("explain"), "promql_range")

            # no flag -> no explain key on any form
            r = await client.post(
                "/api/v1/query",
                json={"metric": "exq", "start_ms": 0, "end_ms": 10_000},
            )
            body = await r.json()
            assert "explain" not in body
            r = await client.get("/api/v1/query?query=exq&time=2")
            body = await r.json()
            assert "explain" not in body
        finally:
            await client.close()


class TestDebugKernels:
    @async_test
    async def test_kernel_catalog_served(self, tmp_path):
        """/debug/kernels lists the instrumented kernels with compile
        telemetry; importing the ops/ modules alone registers them."""
        import horaedb_tpu.ops.blockagg  # noqa: F401 — registers at import

        client = await make_client(tmp_path)
        try:
            r = await client.get("/debug/kernels")
            assert r.status == 200
            body = await r.json()
            assert isinstance(body["kernels"], list)
            names = {k["kernel"] for k in body["kernels"]}
            # the registry block kernels register at import time
            assert "block_sum_count" in names, sorted(names)
            assert {"total_compiles", "total_compile_seconds"} <= set(
                body["totals"]
            )
            for entry in body["kernels"]:
                assert {"kernel", "compiles", "cache_entries",
                        "compile_seconds"} <= set(entry)
        finally:
            await client.close()


    @async_test
    async def test_device_and_parser_are_reported(self, tmp_path):
        """/debug/kernels names the device as JAX reports it (no `except`:
        a process without a backend must fail, not answer null) and the
        compile-cache directory; buildinfo names the rung of the ingest
        parser chain the process took. chip_smoke.py reads both."""
        import jax

        from horaedb_tpu.ingest.pooled_parser import parser_backend

        client = await make_client(tmp_path)
        try:
            body = await (await client.get("/debug/kernels")).json()
            dev = jax.devices()[0]
            assert body["platform"] == dev.platform == "cpu"
            assert body["device_kind"] == dev.device_kind
            assert body["device_count"] == len(jax.devices())
            assert "compile_cache_dir" in body
            info = await (await client.get("/api/v1/status/buildinfo")).json()
            assert info["data"]["parser_backend"] == parser_backend()
            assert parser_backend() in ("native", "protobuf", "wire")
        finally:
            await client.close()


class TestDebugMemory:
    @async_test
    async def test_debug_memory_renders_all_pools(self, tmp_path):
        """/debug/memory: the unified registry's occupancy snapshot —
        all four pools with the pinned row shape, the process RSS, the
        memtrace mode, and the per-stage copy-tax table (non-empty after
        one write+query touched the data plane)."""
        from horaedb_tpu.common.bytebudget import POOLS

        client = await make_client(tmp_path)
        try:
            payload = make_remote_write(
                [({"__name__": "memq", "host": "a"}, [(1000, 1.0)])]
            )
            r = await client.post("/api/v1/write", data=payload)
            assert r.status == 200
            r = await client.post(
                "/api/v1/query",
                json={"metric": "memq", "start_ms": 0, "end_ms": 10_000},
            )
            assert r.status == 200
            r = await client.get("/debug/memory")
            assert r.status == 200
            body = await r.json()
            assert set(POOLS) <= set(body["pools"]), sorted(body["pools"])
            for pool, row in body["pools"].items():
                assert {"bytes", "entries", "capacity_bytes",
                        "utilization", "evictions", "owners"} <= set(row)
            assert body["memtrace_mode"] in ("default", "deep", "off")
            assert body["rss_bytes"] is None or body["rss_bytes"] > 0
            tax = body["copy_tax"]
            assert isinstance(tax, list) and tax, "copy-tax table empty"
            for trow in tax:
                assert {"stage", "kind", "events", "bytes"} <= set(trow)
            assert any(trow["stage"] == "flush_encode" for trow in tax)
        finally:
            await client.close()


class TestSlowlogEndpoint:
    @async_test
    async def test_query_lands_in_slowlog_and_survives(self, tmp_path):
        """A query request spools into <data>/slowlog (default min
        duration 0 admits it), /debug/slowlog serves it with its trace
        tree + explain payload, and a second server over the same data
        dir still sees it (restart survival through the HTTP surface)."""
        from horaedb_tpu.common import tracing

        tracing.configure(sample=1.0)
        client = await make_client(tmp_path)
        try:
            payload = make_remote_write(
                [({"__name__": "slowm", "host": "a"}, [(1000, 5.0)])]
            )
            r = await client.post("/api/v1/write", data=payload)
            assert r.status == 200
            r = await client.post(
                "/api/v1/query",
                json={"metric": "slowm", "start_ms": 0, "end_ms": 10_000},
            )
            assert r.status == 200
            trace_id = r.headers["X-Horaedb-Trace-Id"]
            r = await client.get("/debug/slowlog")
            body = await r.json()
            assert body["enabled"] is True
            ids = [e["trace_id"] for e in body["entries"]]
            assert trace_id in ids, body
            entry = next(e for e in body["entries"]
                         if e["trace_id"] == trace_id)
            assert entry["trace"]["root"]["name"] == "POST /api/v1/query"
            # the recorder carries the full plan even though the caller
            # never sent ?explain=1
            assert entry["explain"]["mode"] == "raw"
            assert EXPLAIN_KEYS <= set(entry["explain"])
            # the memory verdict is surfaced top-level (satellite of the
            # memory observatory): triage reads it without unpacking the
            # full plan
            assert entry["memory"] == entry["explain"]["memory"]
            assert entry["memory"]["enabled"] is True
            # writes (non-query endpoints) never spool
            assert all(
                e["trace"]["root"]["name"] != "POST /api/v1/write"
                for e in body["entries"]
            )
            # ?limit= bounds the response
            r = await client.get("/debug/slowlog?limit=0")
            body = await r.json()
            assert body["entries"] == []
        finally:
            await client.close()
        # restart over the same data dir: the spool is durable
        client2 = await make_client(tmp_path)
        try:
            r = await client2.get("/debug/slowlog")
            body = await r.json()
            assert trace_id in [e["trace_id"] for e in body["entries"]]
        finally:
            await client2.close()

    @async_test
    async def test_slowlog_disabled_by_config(self, tmp_path):
        cfg = Config.from_toml(
            f"""
port = 0
[slowlog]
capacity = 0
[metric_engine.storage.object_store]
type = "Local"
data_dir = "{tmp_path}/data"
"""
        )
        app = await build_app(cfg)
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            r = await client.get("/debug/slowlog")
            body = await r.json()
            assert body == {"enabled": False, "capacity": 0, "entries": []}
        finally:
            await client.close()

    def test_slowlog_config_parses_and_validates(self):
        c = Config.from_toml(
            '[slowlog]\ncapacity = 5\nmin_duration = "100ms"\n'
        )
        c.validate()
        assert c.slowlog.capacity == 5
        assert c.slowlog.min_duration.as_millis() == 100
        with pytest.raises(HoraeError, match="slowlog.capacity"):
            Config.from_toml("[slowlog]\ncapacity = -1\n").validate()


class TestMetadata:
    @async_test
    async def test_metadata_roundtrip(self, tmp_path):
        """Remote-write METADATA records surface at /api/v1/metadata
        (Prometheus response shape; advisory, in-memory)."""
        from horaedb_tpu.pb import remote_write_pb2

        client = await make_client(tmp_path)
        try:
            req = remote_write_pb2.WriteRequest()
            for name, t in ((b"cpu_seconds_total", 1), (b"mem_bytes", 2)):
                md = req.metadata.add()
                md.type = t
                md.metric_family_name = name
            # metadata-only payload (no series): must still be recorded
            r = await client.post("/api/v1/write", data=req.SerializeToString())
            assert r.status == 200

            r = await client.get("/api/v1/metadata")
            assert r.status == 200
            body = await r.json()
            assert body["status"] == "success"
            assert body["data"]["cpu_seconds_total"] == [{"type": "counter"}]
            assert body["data"]["mem_bytes"] == [{"type": "gauge"}]

            # out-of-range enum values clamp to "unknown"
            req2 = remote_write_pb2.WriteRequest()
            md = req2.metadata.add()
            md.type = 99
            md.metric_family_name = b"mystery"
            r = await client.post("/api/v1/write", data=req2.SerializeToString())
            assert r.status == 200
            body = await (await client.get("/api/v1/metadata")).json()
            assert body["data"]["mystery"] == [{"type": "unknown"}]
        finally:
            await client.close()

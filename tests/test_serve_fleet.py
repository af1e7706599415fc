"""Fleet serving (PR 13): supervised replica sets, health-aware routing,
zero-drop promotion.

Tier-1 stories:
- a chaos-killed replica is routed around, restarted warm, and
  re-admitted — zero client-visible 5xx under load;
- a rolling promotion of a healthy-stamped checkpoint drops zero
  in-flight requests and keeps p99 under the SLO;
- an unhealthy promotion (chaos taint, dirty stamp, failed shadow gate)
  is refused loudly and the old model keeps serving.

Plus the satellites: Retry-After on shed/drain replies, the richer
/healthz body, knob registry coverage, strict-vs-tolerant meta readers
on truncated/garbage files, the doctor ``fleet`` section, and the
per-replica analyzer breakout.
"""

import json
import os
import urllib.error
import urllib.request

import numpy as np
import pytest

jnp = pytest.importorskip("jax.numpy")


def _linear_model(item_shape=(4, 3), classes=3, seed=0):
    n = int(np.prod(item_shape))
    W = np.random.RandomState(seed).rand(n, classes).astype(np.float32)

    def fn(x):
        return jnp.asarray(x).reshape(x.shape[0], -1) @ W

    return fn, W


def _knobs(**over):
    from tpuframe.serve import ServeKnobs

    kn = dict(buckets=(1, 4), slo_ms=5000, queue_cap=64, batch_wait_ms=1.0)
    kn.update(over)
    return ServeKnobs(**kn)


def _blob(seed=0):
    import io

    buf = io.BytesIO()
    np.save(buf, np.random.RandomState(seed).rand(4, 3).astype(np.float32))
    return buf.getvalue()


def _post(url, blob, timeout=10.0):
    req = urllib.request.Request(
        url + "/predict", data=blob, method="POST",
        headers={"Content-Type": "application/octet-stream"},
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, json.loads(resp.read()), dict(resp.headers)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"{}"), dict(e.headers)


def _fleet(n=2, **fleet_over):
    from tpuframe.serve import ReplicaSet
    from tpuframe.serve.router import FleetKnobs

    fn, W = _linear_model()
    fk = dict(probe_ms=25.0, retries=2, retry_budget=0.5, replicas=n,
              shadow_requests=8, gate_agreement=0.99)
    fk.update(fleet_over)
    fleet = ReplicaSet(
        fn, n=n, serve_knobs=_knobs(), fleet_knobs=FleetKnobs(**fk),
        item_shape=(4, 3), dtype="float32",
    )
    return fleet, W


# ===========================================================================
# knobs + registry (satellite 3)
# ===========================================================================


class TestFleetKnobs:
    def test_defaults(self):
        from tpuframe.serve.router import FleetKnobs

        k = FleetKnobs()
        assert k.probe_ms == 50.0 and k.retries == 2
        assert k.replicas == 3 and 0 < k.gate_agreement <= 1.0

    def test_from_env_overrides_and_clamps(self, monkeypatch):
        from tpuframe.serve.router import FleetKnobs

        monkeypatch.setenv("TPUFRAME_ROUTER_PROBE_MS", "10")
        monkeypatch.setenv("TPUFRAME_ROUTER_RETRIES", "-3")
        monkeypatch.setenv("TPUFRAME_ROUTER_RETRY_BUDGET", "7.5")
        monkeypatch.setenv("TPUFRAME_FLEET_REPLICAS", "0")
        monkeypatch.setenv("TPUFRAME_FLEET_GATE_AGREEMENT", "0.5")
        k = FleetKnobs.from_env()
        assert k.probe_ms == 10.0
        assert k.retries == 0          # clamped up from -3
        assert k.retry_budget == 1.0   # clamped down from 7.5
        assert k.replicas == 1         # a zero-replica fleet is no fleet
        assert k.gate_agreement == 0.5

    def test_malformed_env_reads_as_default(self, monkeypatch):
        from tpuframe.serve.router import FleetKnobs

        monkeypatch.setenv("TPUFRAME_ROUTER_PROBE_MS", "soon")
        assert FleetKnobs.from_env().probe_ms == FleetKnobs().probe_ms

    def test_every_fleet_knob_is_registered(self):
        from tpuframe.serve.admission import SERVE_ENV_DOMAINS, SERVE_ENV_VARS

        fleet_vars = [v for v in SERVE_ENV_VARS
                      if v.startswith(("TPUFRAME_ROUTER_", "TPUFRAME_FLEET_"))]
        assert len(fleet_vars) == 6
        assert set(SERVE_ENV_DOMAINS) == set(SERVE_ENV_VARS)
        for v in fleet_vars:
            assert SERVE_ENV_DOMAINS[v]["apply"] == "restart"


# ===========================================================================
# router unit behavior (tentpole, no replicas needed)
# ===========================================================================


class TestRouterUnit:
    def test_no_backend_is_503_with_retry_after(self):
        from tpuframe.serve.router import Router

        r = Router()  # never started: zero backends
        status, body, headers = r.handle_predict(_blob(), {})
        assert status == 503
        doc = json.loads(body)
        assert doc["verdict"] == "no-backend"
        assert int(headers["Retry-After"]) >= 1

    def test_pick_is_least_loaded(self):
        from tpuframe.serve.router import Router, _Backend

        r = Router()
        for url, depth in [("http://x:1", 9), ("http://x:2", 1),
                           ("http://x:3", 4)]:
            b = _Backend(url)
            b.healthy, b.queue_depth = True, depth
            r._backends[url] = b
        assert r._pick(set()) == "http://x:2"
        assert r._pick({"http://x:2"}) == "http://x:3"

    def test_pick_skips_draining_and_unhealthy(self):
        from tpuframe.serve.router import Router, _Backend

        r = Router()
        a, b = _Backend("http://x:1"), _Backend("http://x:2")
        a.healthy, a.draining = True, True
        b.healthy = False
        r._backends.update({a.url: a, b.url: b})
        assert r._pick(set()) is None

    def test_retry_budget_caps_amplification(self):
        from tpuframe.serve.router import FleetKnobs, Router

        # counters are process-global: drive the gate relative to
        # whatever the registry already holds
        r = Router(knobs=FleetKnobs(retry_budget=0.2))
        spins = 0
        while r._retry_allowed():
            r._c_retries.inc()
            spins += 1
            assert spins < 10_000, "retry budget never closed"
        cap = r.knobs.retry_budget * r._c_requests.value + 1
        assert r._c_retries.value >= cap
        r._c_requests.inc(100)     # fresh traffic replenishes the budget
        assert r._retry_allowed()

    def test_payload_mirror_ring_is_bounded(self):
        from tpuframe.serve.router import Router

        r = Router()
        for i in range(r.MIRROR_RING + 7):
            with r._lock:
                r._mirror.append(bytes([i % 251]))
        assert len(r.recent_payloads()) == r.MIRROR_RING


# ===========================================================================
# server satellites: Retry-After + richer /healthz
# ===========================================================================


class TestServerFleetFacing:
    def test_healthz_carries_queue_depth_and_draining(self):
        from tpuframe.serve import ServeEngine, ServingServer

        fn, _ = _linear_model()
        eng = ServeEngine(fn, knobs=_knobs(), item_shape=(4, 3),
                          dtype="float32").start()
        srv = ServingServer(eng, port=0)
        try:
            with urllib.request.urlopen(srv.url + "/healthz", timeout=5) as r:
                doc = json.loads(r.read())
            assert doc["status"] == "ok"
            assert doc["draining"] is False
            assert isinstance(doc["queue_depth"], int)
        finally:
            srv.close()
            eng.stop()

    def test_draining_replica_503s_with_retry_after(self):
        from tpuframe.serve import ServeEngine, ServingServer

        fn, _ = _linear_model()
        eng = ServeEngine(fn, knobs=_knobs(), item_shape=(4, 3),
                          dtype="float32").start()
        srv = ServingServer(eng, port=0)
        try:
            assert eng.drain(timeout=10.0)
            status, doc, headers = _post(srv.url, _blob())
            assert status == 503
            assert doc["verdict"] == "rejected-draining"
            assert 1 <= int(headers["Retry-After"]) <= 30
            with urllib.request.urlopen(srv.url + "/healthz", timeout=5) as r:
                hz = json.loads(r.read())
            assert hz["status"] == "draining" and hz["draining"] is True
        finally:
            srv.close()
            eng.stop()

    def test_retry_after_scales_with_queue_depth(self):
        from tpuframe.serve import ServeEngine, ServingServer

        fn, _ = _linear_model()
        eng = ServeEngine(fn, knobs=_knobs(batch_wait_ms=1000.0),
                          item_shape=(4, 3), dtype="float32")
        srv = ServingServer(eng, port=0)
        try:
            handler = srv._retry_after
            hdr = handler()
            assert 1 <= int(hdr["Retry-After"]) <= 30
        finally:
            srv.close()


# ===========================================================================
# strict vs tolerant meta readers (satellite 4)
# ===========================================================================


def _committed_step(tmp_path, step=100, meta=None, meta_bytes=None):
    d = tmp_path / "ckpt"
    sd = d / str(step)
    (sd / "meta").mkdir(parents=True)
    (sd / "_CHECKPOINT_METADATA").write_text("{}")
    if meta_bytes is not None:
        (sd / "meta" / "metadata").write_bytes(meta_bytes)
    elif meta is not None:
        (sd / "meta" / "metadata").write_text(json.dumps(meta))
    return str(d)


class TestCkptHealthVerdict:
    """The promotion gate refuses loudly on anything it cannot
    positively read — it never crashes, and it never silently passes a
    corrupt candidate."""

    def test_empty_dir_refuses(self, tmp_path):
        from tpuframe.ckpt import ckpt_health_verdict

        ok, reason = ckpt_health_verdict(str(tmp_path))
        assert not ok and "no committed" in reason

    def test_torn_step_refuses(self, tmp_path):
        from tpuframe.ckpt import ckpt_health_verdict

        (tmp_path / "50").mkdir()  # digit dir, no commit marker
        ok, reason = ckpt_health_verdict(str(tmp_path), 50)
        assert not ok and "commit marker" in reason

    def test_pre_sentinel_checkpoint_passes(self, tmp_path):
        from tpuframe.ckpt import ckpt_health_verdict

        d = _committed_step(tmp_path)  # committed, no meta file at all
        ok, reason = ckpt_health_verdict(d, 100)
        assert ok and "pre-sentinel" in reason

    def test_garbage_meta_refuses_not_crashes(self, tmp_path):
        from tpuframe.ckpt import ckpt_health_verdict

        d = _committed_step(tmp_path, meta_bytes=b"\x00\xffnot json at all")
        ok, reason = ckpt_health_verdict(d, 100)
        assert not ok and "unreadable" in reason

    def test_truncated_meta_refuses_not_crashes(self, tmp_path):
        from tpuframe.ckpt import ckpt_health_verdict

        full = json.dumps({"health": {"healthy": True}})
        d = _committed_step(tmp_path,
                            meta_bytes=full[: len(full) // 2].encode())
        ok, reason = ckpt_health_verdict(d, 100)
        assert not ok and "unreadable" in reason

    def test_non_dict_meta_refuses(self, tmp_path):
        from tpuframe.ckpt import ckpt_health_verdict

        d = _committed_step(tmp_path, meta_bytes=b"[1, 2, 3]")
        ok, reason = ckpt_health_verdict(d, 100)
        assert not ok and "not a JSON object" in reason

    def test_malformed_health_stamp_refuses(self, tmp_path):
        from tpuframe.ckpt import ckpt_health_verdict

        d = _committed_step(tmp_path, meta={"health": "fine, trust me"})
        ok, reason = ckpt_health_verdict(d, 100)
        assert not ok and "malformed" in reason

    def test_unhealthy_stamp_refuses(self, tmp_path):
        from tpuframe.ckpt import ckpt_health_verdict

        d = _committed_step(tmp_path, meta={"health": {"healthy": False}})
        ok, reason = ckpt_health_verdict(d, 100)
        assert not ok and "unhealthy" in reason

    def test_clean_stamp_passes(self, tmp_path):
        from tpuframe.ckpt import ckpt_health_verdict

        d = _committed_step(
            tmp_path, meta={"health": {"healthy": True, "bad_steps": 0}})
        ok, reason = ckpt_health_verdict(d, 100)
        assert ok and "clean" in reason

    def test_tolerant_read_health_stays_tolerant(self, tmp_path):
        """read_health (doctor-shaped) returns None on the same garbage
        the strict gate refuses — both must survive, neither crashes."""
        from tpuframe.ckpt import read_health

        d = _committed_step(tmp_path, meta_bytes=b"\x00garbage")
        assert read_health(d, 100) is None
        assert read_health(d) is None


class TestReadExportMetaRobustness:
    def test_truncated_file_is_valueerror(self, tmp_path):
        from tpuframe.serve.admission import read_export_meta

        p = tmp_path / "export.tpuf"
        p.write_bytes(b"\x03")  # shorter than the 8-byte length prefix
        with pytest.raises(ValueError, match="not a tpuframe export"):
            read_export_meta(p)

    def test_huge_declared_header_is_valueerror_not_oom(self, tmp_path):
        from tpuframe.serve.admission import read_export_meta

        p = tmp_path / "export.tpuf"
        p.write_bytes((2**62).to_bytes(8, "little") + b"xx")
        with pytest.raises(ValueError, match="not a tpuframe export"):
            read_export_meta(p)

    def test_garbage_header_bytes_are_valueerror(self, tmp_path):
        from tpuframe.serve.admission import read_export_meta

        p = tmp_path / "export.tpuf"
        p.write_bytes((4).to_bytes(8, "little") + b"\xff\xfe\x00\x01")
        with pytest.raises(ValueError, match="not a tpuframe export"):
            read_export_meta(p)


# ===========================================================================
# chaos story (a): ReplicaKill under load (tentpole)
# ===========================================================================


@pytest.mark.chaos
class TestReplicaKillStory:
    def test_kill_routes_around_and_restarts_warm(self):
        import time

        from tpuframe.fault import ChaosPlan, ReplicaKill
        from tpuframe.track.telemetry import get_telemetry

        reg = get_telemetry().registry
        restarts0 = reg.counter("fleet/restarts").value
        compiles0 = (reg.counter("compile/compiles").value
                     + reg.counter("compile/recompiles").value)

        fleet, _ = _fleet(n=2, probe_ms=20.0)
        plan = ChaosPlan([ReplicaKill(step=3)])
        statuses: dict[int, int] = {}
        with fleet, plan.active():
            url = fleet.router.url
            deadline = time.monotonic() + 2.0
            i = 0
            while time.monotonic() < deadline:
                status, _, _ = _post(url, _blob(i))
                statuses[status] = statuses.get(status, 0) + 1
                i += 1
            # wait for the supervisor to bring the killed replica back
            # green: detection + backoff + rebuild, all bounded
            for _ in range(200):
                if len(fleet.router.healthy_backends()) == 2:
                    break
                time.sleep(0.05)
            assert len(fleet.router.healthy_backends()) == 2

        # zero client-visible 5xx: every request either served or was
        # retried onto the surviving replica within budget
        assert set(statuses) == {200}, statuses
        assert statuses[200] == i > 0
        # the kill burned exactly restart budget, not compile budget:
        # the rebuilt replica came back warm off the persistent cache
        assert reg.counter("fleet/restarts").value >= restarts0 + 1
        compiles1 = (reg.counter("compile/compiles").value
                     + reg.counter("compile/recompiles").value)
        assert compiles1 == compiles0, "restart must be warm (AOT cache)"

    def test_replica_kill_without_ctx_is_misconfigured_drill(self):
        from tpuframe.fault import ReplicaKill

        with pytest.raises(ValueError, match="fleet/replica"):
            ReplicaKill(step=0).fire({"step": 0})


# ===========================================================================
# stories (b) + (c): promotion — zero-drop roll vs loud refusal
# ===========================================================================


@pytest.mark.chaos
class TestPromotionStories:
    def test_rolling_promotion_drops_nothing(self):
        from tpuframe.track.telemetry import get_telemetry

        reg = get_telemetry().registry
        promoted0 = reg.counter("fleet/promotions").value
        fleet, W = _fleet(n=2)
        fn2, _ = _linear_model(seed=0)  # same weights: agreement == 1.0
        with fleet:
            for i in range(6):  # real mirrored traffic for the shadow gate
                status, _, _ = _post(fleet.router.url, _blob(i))
                assert status == 200
            gen0 = fleet.generation
            out = fleet.promote(fn2, timeout_s=30.0)
            assert out["swapped"] == 2
            assert out["dropped_in_flight"] == 0
            assert out["agreement"] >= 0.99
            assert out["generation"] == gen0 + 1
            # the rolled fleet still serves
            status, doc, _ = _post(fleet.router.url, _blob(99))
            assert status == 200 and doc["verdict"] == "ok"
        assert reg.counter("fleet/promotions").value == promoted0 + 1

    def test_promotion_gated_on_checkpoint_stamp(self, tmp_path):
        from tpuframe.serve import PromotionRefused

        fleet, _ = _fleet(n=1)
        fn2, _ = _linear_model(seed=0)
        dirty = _committed_step(tmp_path,
                                meta={"health": {"healthy": False}})
        with fleet:
            with pytest.raises(PromotionRefused, match="unhealthy"):
                fleet.promote(fn2, ckpt_dir=dirty, step=100)
            # the old model keeps serving
            status, _, _ = _post(fleet.router.url, _blob())
            assert status == 200

    def test_promotion_gated_on_garbage_stamp(self, tmp_path):
        from tpuframe.serve import PromotionRefused

        fleet, _ = _fleet(n=1)
        fn2, _ = _linear_model(seed=0)
        garbage = _committed_step(tmp_path, meta_bytes=b"\x00not json")
        with fleet:
            with pytest.raises(PromotionRefused, match="unreadable"):
                fleet.promote(fn2, ckpt_dir=garbage, step=100)
            status, _, _ = _post(fleet.router.url, _blob())
            assert status == 200

    def test_shadow_gate_refuses_a_disagreeing_candidate(self):
        from tpuframe.serve import PromotionRefused
        from tpuframe.track.telemetry import get_telemetry

        reg = get_telemetry().registry
        refused0 = reg.counter("fleet/promotions_refused").value
        fleet, W = _fleet(n=1)

        def hostile(x):  # argmax-inverts every prediction: agreement 0
            return -(jnp.asarray(x).reshape(x.shape[0], -1) @ W)

        with fleet:
            for i in range(6):
                _post(fleet.router.url, _blob(i))
            before = _post(fleet.router.url, _blob(7))[1]["output"]
            with pytest.raises(PromotionRefused, match="agreement"):
                fleet.promote(hostile)
            after = _post(fleet.router.url, _blob(7))[1]["output"]
            np.testing.assert_allclose(before, after, rtol=1e-5)
        assert reg.counter("fleet/promotions_refused").value >= refused0 + 1

    def test_unhealthy_promotion_chaos_taints_the_candidate(self, tmp_path):
        from tpuframe.fault import ChaosPlan, UnhealthyPromotion
        from tpuframe.serve import PromotionRefused

        fleet, _ = _fleet(n=1)
        fn2, _ = _linear_model(seed=0)
        clean = _committed_step(tmp_path,
                                meta={"health": {"healthy": True}})
        # step=None: fire on the first promote attempt this fleet makes
        with fleet, ChaosPlan([UnhealthyPromotion()]).active():
            with pytest.raises(PromotionRefused, match="chaos"):
                fleet.promote(fn2, ckpt_dir=clean, step=100)
            status, _, _ = _post(fleet.router.url, _blob())
            assert status == 200

    def test_unhealthy_promotion_without_ctx_is_misconfigured(self):
        from tpuframe.fault import UnhealthyPromotion

        with pytest.raises(ValueError, match="fleet/promote"):
            UnhealthyPromotion(step=0).fire({"step": 0})


# ===========================================================================
# doctor + analyzer satellites
# ===========================================================================


class TestDoctorFleetSection:
    def test_section_shape(self, monkeypatch, scripts_not_in_tree):
        from tpuframe.doctor import fleet_section

        monkeypatch.setenv("TPUFRAME_FLEET_REPLICAS", "5")
        sec = fleet_section()
        assert sec["knobs"]["replicas"] == 5
        assert sec["env"] == {"TPUFRAME_FLEET_REPLICAS": "5"}
        assert sec["detection_window_ms"] == sec["knobs"]["probe_ms"]
        assert scripts_not_in_tree(sec) == []

    def test_report_includes_fleet(self):
        from tpuframe.doctor import report

        assert "fleet" in report()


class TestAnalyzePerReplica:
    def test_replica_tagged_requests_break_out(self, tmp_path):
        from tpuframe.serve import ServeEngine
        from tpuframe.track import telemetry as T
        from tpuframe.track.analyze import load_dir, skew_report

        fn, _ = _linear_model()
        T.configure(jsonl_dir=str(tmp_path), rank=0)
        try:
            for rep in (0, 1):
                eng = ServeEngine(fn, knobs=_knobs(), item_shape=(4, 3),
                                  dtype="float32", replica=rep)
                with eng:
                    for i in range(5):
                        eng.submit(
                            np.random.RandomState(i).rand(4, 3)
                            .astype(np.float32)).result(timeout=10)
        finally:
            T.reset()
        sv = skew_report(load_dir(str(tmp_path)))["serve_latency"]
        assert sv["count"] == 10
        assert sv["replicas"] == 2
        assert set(sv["per_replica"]) == {"0", "1"} or \
            set(sv["per_replica"]) == {0, 1}
        for block in sv["per_replica"].values():
            assert block["count"] == 5 and block["p50"] <= block["p99"]


# ===========================================================================
# request-path tracing + SLO plane (PR 16)
# ===========================================================================


def _post_traced(url, blob, headers=None, timeout=10.0):
    hdrs = {"Content-Type": "application/octet-stream"}
    hdrs.update(headers or {})
    req = urllib.request.Request(url + "/predict", data=blob, method="POST",
                                 headers=hdrs)
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, json.loads(resp.read()), dict(resp.headers)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"{}"), dict(e.headers)


def _jsonl_events(d):
    import glob

    evs = []
    for p in sorted(glob.glob(os.path.join(str(d), "events-rank*.jsonl"))):
        with open(p) as f:
            for line in f:
                line = line.strip()
                if line:
                    try:
                        evs.append(json.loads(line))
                    except ValueError:
                        pass  # torn trailing line
    return evs


def _event_trace_ids(ev):
    """Trace ids an event belongs to: a per-request ``trace`` field
    (top-level or span attrs) or a batch-scoped ``traces`` fan-out."""
    attrs = ev.get("attrs") or {}
    one = ev.get("trace") or attrs.get("trace")
    many = ev.get("traces") or attrs.get("traces") or []
    return ([one] if one else []) + list(many)


class TestTraceIdSanitizer:
    def test_accepts_sane_ids_and_strips(self):
        from tpuframe.serve import sanitize_trace_id

        assert sanitize_trace_id("abc-123_X.y") == "abc-123_X.y"
        assert sanitize_trace_id("  ok  ") == "ok"

    def test_rejects_garbage(self):
        from tpuframe.serve import sanitize_trace_id

        assert sanitize_trace_id(None) is None
        assert sanitize_trace_id("") is None
        assert sanitize_trace_id("   ") is None
        assert sanitize_trace_id("evil\nheader") is None
        assert sanitize_trace_id("x" * 65) is None
        assert sanitize_trace_id(123) is None


class TestTracePropagation:
    def test_client_trace_spans_router_to_engine(self, tmp_path):
        """One client-supplied trace id must appear on every hop from the
        router's pick to the response write — the tentpole story."""
        from tpuframe.serve import ServeEngine, ServingServer
        from tpuframe.serve.router import Router
        from tpuframe.track import telemetry as T

        T.configure(jsonl_dir=str(tmp_path), rank=0)
        try:
            fn, _ = _linear_model()
            eng = ServeEngine(fn, knobs=_knobs(), item_shape=(4, 3),
                              dtype="float32").start()
            srv = ServingServer(eng, port=0)
            router = Router([srv.url]).start()
            try:
                status, doc, headers = _post_traced(
                    router.url, _blob(),
                    headers={"X-Trace-Id": "trace-fleet-1"})
                assert status == 200
                assert headers["X-Trace-Id"] == "trace-fleet-1"
            finally:
                router.close()
                srv.close()
                eng.stop()
        finally:
            T.reset()
        names = {e["name"] for e in _jsonl_events(tmp_path)
                 if "trace-fleet-1" in _event_trace_ids(e)}
        assert {"fleet/route", "fleet/hop", "serve/door", "serve/queue_wait",
                "serve/assemble", "serve/infer", "serve/respond"} <= names

    def test_router_mints_when_client_sends_none(self):
        from tpuframe.serve import ServeEngine, ServingServer
        from tpuframe.serve.router import Router

        fn, _ = _linear_model()
        eng = ServeEngine(fn, knobs=_knobs(), item_shape=(4, 3),
                          dtype="float32").start()
        srv = ServingServer(eng, port=0)
        router = Router([srv.url]).start()
        try:
            status, _, headers = _post_traced(router.url, _blob())
            assert status == 200
            minted = headers["X-Trace-Id"]
            assert len(minted) == 16
            int(minted, 16)  # hex
            # a garbage client id is replaced by a minted one, not echoed
            status, _, headers = _post_traced(
                router.url, _blob(), headers={"X-Trace-Id": "x" * 65})
            assert status == 200
            assert len(headers["X-Trace-Id"]) == 16
        finally:
            router.close()
            srv.close()
            eng.stop()

    def test_direct_server_hit_is_untraced(self):
        """The replica propagates but never mints: a direct hit without
        the header is the traced-off baseline (no response header, no
        hop records)."""
        from tpuframe.serve import ServeEngine, ServingServer

        fn, _ = _linear_model()
        eng = ServeEngine(fn, knobs=_knobs(), item_shape=(4, 3),
                          dtype="float32").start()
        srv = ServingServer(eng, port=0)
        try:
            status, _, headers = _post(srv.url, _blob())
            assert status == 200
            assert "X-Trace-Id" not in headers
        finally:
            srv.close()
            eng.stop()

    def test_server_echoes_and_engine_records_client_trace(self, tmp_path):
        from tpuframe.serve import ServeEngine, ServingServer
        from tpuframe.track import telemetry as T

        T.configure(jsonl_dir=str(tmp_path), rank=0)
        try:
            fn, _ = _linear_model()
            eng = ServeEngine(fn, knobs=_knobs(), item_shape=(4, 3),
                              dtype="float32").start()
            srv = ServingServer(eng, port=0)
            try:
                status, _, headers = _post_traced(
                    srv.url, _blob(), headers={"X-Trace-Id": "direct-1"})
                assert status == 200
                assert headers["X-Trace-Id"] == "direct-1"
            finally:
                srv.close()
                eng.stop()
        finally:
            T.reset()
        tagged = [e for e in _jsonl_events(tmp_path)
                  if "direct-1" in _event_trace_ids(e)]
        names = {e["name"] for e in tagged}
        assert {"serve/door", "serve/queue_wait", "serve/assemble",
                "serve/infer", "serve/respond"} <= names
        # the served request record itself carries the trace id too
        assert any(e["name"] == "serve/request" for e in tagged)


class TestMarkdownMarkupEvents:
    def test_mark_down_emits_event_and_counter(self, tmp_path):
        from tpuframe.serve.router import Router, _Backend
        from tpuframe.track import telemetry as T

        T.configure(jsonl_dir=str(tmp_path), rank=0)
        try:
            r = Router()
            b = _Backend("http://x:1")
            b.healthy = True
            r._backends[b.url] = b
            before = r._c_markdowns.value
            r._mark_down(b.url, "connect-refused")
            assert r._c_markdowns.value == before + 1
            # a second mark-down of an already-down replica is a no-op
            r._mark_down(b.url, "connect-refused")
            assert r._c_markdowns.value == before + 1
        finally:
            T.reset()
        evs = [e for e in _jsonl_events(tmp_path)
               if e["name"] == "fleet/markdown"]
        assert len(evs) == 1
        assert evs[0]["replica"] == "http://x:1"
        assert evs[0]["reason"] == "connect-refused"

    def test_probe_transitions_emit_markdown_and_markup(self, tmp_path):
        from tpuframe.serve import ServeEngine, ServingServer
        from tpuframe.serve.router import Router
        from tpuframe.track import telemetry as T

        T.configure(jsonl_dir=str(tmp_path), rank=0)
        try:
            fn, _ = _linear_model()
            eng = ServeEngine(fn, knobs=_knobs(), item_shape=(4, 3),
                              dtype="float32").start()
            srv = ServingServer(eng, port=0)
            r = Router()
            try:
                r.add_backend(srv.url)  # probes inline: up-transition
                assert r.healthy_backends() == [srv.url]
                srv.close()             # kill the replica out from under it
                r._probe_once()         # down-transition
                assert r.healthy_backends() == []
            finally:
                r.close()
                srv.close()
                eng.stop()
        finally:
            T.reset()
        evs = _jsonl_events(tmp_path)
        ups = [e for e in evs if e["name"] == "fleet/markup"]
        downs = [e for e in evs if e["name"] == "fleet/markdown"]
        assert any(e["replica"] == srv.url and e["reason"] == "probe"
                   for e in ups)
        assert any(e["replica"] == srv.url and e["reason"] == "probe"
                   for e in downs)


class TestRouterMetricsAggregation:
    def test_one_scrape_returns_replica_labeled_gauges(self):
        from tpuframe.serve import ServeEngine, ServingServer
        from tpuframe.serve.router import Router

        fn, _ = _linear_model()
        eng = ServeEngine(fn, knobs=_knobs(), item_shape=(4, 3),
                          dtype="float32").start()
        srv = ServingServer(eng, port=0)
        router = Router([srv.url]).start()
        try:
            with urllib.request.urlopen(router.url + "/metrics",
                                        timeout=5) as resp:
                text = resp.read().decode()
            label = '{replica="' + srv.url + '"}'
            assert f"tpuframe_serve_queue_depth{label}" in text
            assert f"tpuframe_fleet_replica_healthy{label} 1" in text
            assert f"tpuframe_fleet_replica_draining{label} 0" in text
            assert f"tpuframe_fleet_replica_ewma_seconds{label}" in text
            # the fleet-wide SLO aggregate rides the same page
            assert "tpuframe_slo_burn_rate" in text
            assert "tpuframe_slo_error_budget" in text
        finally:
            router.close()
            srv.close()
            eng.stop()

    def test_labeled_lines_do_not_fool_the_depth_scraper(self):
        """A router scraped as if it were a replica must not leak a
        labeled per-replica depth into the unlabeled-gauge fallback."""
        from tpuframe.serve.router import Router, _Backend

        r = Router()
        b = _Backend("http://x:1")
        b.healthy, b.queue_depth = True, 7
        r._backends[b.url] = b
        for line in r._fleet_metrics_text().splitlines():
            assert not line.startswith("tpuframe_serve_queue_depth ")

    def test_healthz_reports_green_count(self):
        from tpuframe.serve import ServeEngine, ServingServer
        from tpuframe.serve.router import Router

        fn, _ = _linear_model()
        eng = ServeEngine(fn, knobs=_knobs(), item_shape=(4, 3),
                          dtype="float32").start()
        srv = ServingServer(eng, port=0)
        router = Router([srv.url]).start()
        try:
            with urllib.request.urlopen(router.url + "/healthz",
                                        timeout=5) as resp:
                doc = json.loads(resp.read())
            assert doc["healthy"] == 1 and doc["green"] == 1
            eng.drain(timeout=10.0)   # healthy but draining: not green
            router._probe_once()
            with urllib.request.urlopen(router.url + "/healthz",
                                        timeout=5) as resp:
                doc = json.loads(resp.read())
            assert doc["green"] == 0
        finally:
            router.close()
            srv.close()
            eng.stop()


class TestRetryAfterClampBounds:
    """Satellite: the Retry-After estimate is clamped to [1, 30] at both
    ends, whatever the queue/batch-wait arithmetic says."""

    class _FakeEngine:
        item_shape = (2,)
        dtype = "float32"
        buckets = (1, 4)
        draining = False

        def __init__(self, depth, batch_wait_ms):
            import types

            self._depth = depth
            self.knobs = types.SimpleNamespace(batch_wait_ms=batch_wait_ms)

        def queue_depth(self):
            return self._depth

    def _retry_after(self, depth, batch_wait_ms):
        from tpuframe.serve import ServingServer

        srv = ServingServer(self._FakeEngine(depth, batch_wait_ms), port=0)
        try:
            return int(srv._retry_after()["Retry-After"])
        finally:
            srv.close()

    def test_huge_backlog_clamps_to_30(self):
        assert self._retry_after(10_000, 60_000.0) == 30

    def test_idle_engine_clamps_up_to_1(self):
        assert self._retry_after(0, 0.0) == 1

    def test_mid_range_is_the_honest_estimate(self):
        # 40 queued / bucket 4 = 10 batches x 500ms = 5s
        assert self._retry_after(40, 500.0) == 5


class TestHealthzUnderActiveDrain:
    def test_depth_and_draining_visible_mid_drain(self):
        """Satellite: /healthz must report ``draining: true`` and the
        live queue depth WHILE a drain is in progress, not only after.
        The engine loop is started late so the queued work is pinned in
        place while we scrape."""
        import threading
        import time

        from tpuframe.serve import ServeEngine, ServingServer

        fn, _ = _linear_model()
        eng = ServeEngine(fn, knobs=_knobs(slo_ms=30000), item_shape=(4, 3),
                          dtype="float32")
        # gate the batching loop so the queued work is pinned in place
        # while we scrape mid-drain
        gate = threading.Event()
        orig_gather = eng._gather

        def gated_gather():
            gate.wait(30.0)
            return orig_gather()

        eng._gather = gated_gather
        eng.start()
        srv = ServingServer(eng, port=0)
        try:
            results = [eng.submit(np.random.RandomState(i).rand(4, 3)
                                  .astype(np.float32)) for i in range(5)]
            assert eng.queue_depth() == 5
            t = threading.Thread(target=eng.drain,
                                 kwargs={"timeout": 30.0}, daemon=True)
            t.start()
            hz = None
            deadline = time.monotonic() + 5.0
            while time.monotonic() < deadline:
                with urllib.request.urlopen(srv.url + "/healthz",
                                            timeout=5) as resp:
                    hz = json.loads(resp.read())
                if hz["draining"]:
                    break
                time.sleep(0.01)
            assert hz is not None and hz["draining"] is True
            assert hz["status"] == "draining"
            assert hz["queue_depth"] == 5  # queued work visible mid-drain
            gate.set()  # now let the loop run the queue down
            t.join(timeout=30.0)
            assert not t.is_alive(), "drain never finished"
            for res in results:
                res.result(timeout=10)
            with urllib.request.urlopen(srv.url + "/healthz",
                                        timeout=5) as resp:
                hz = json.loads(resp.read())
            assert hz["queue_depth"] == 0
        finally:
            srv.close()
            eng.stop()


class TestSloPlane:
    def test_burn_rate_math(self):
        from tpuframe.serve import SloObjectives, SloTracker

        t = SloTracker(SloObjectives(p99_ms=500.0, availability=0.999),
                       window_s=60.0)
        for _ in range(10):
            t.observe(0.1)       # well under the objective
        t.observe(0.9)           # latency violation
        t.observe(ok=False)      # availability violation
        snap = t.snapshot()
        assert snap["requests"] == 12 and snap["violations"] == 2
        assert snap["burn_rate"] == pytest.approx((2 / 12) / 0.001, rel=1e-3)
        assert snap["error_budget_remaining"] == 0.0

    def test_clean_window_has_zero_burn(self):
        from tpuframe.serve import SloObjectives, SloTracker

        t = SloTracker(SloObjectives(p99_ms=500.0, availability=0.999))
        for _ in range(5):
            t.observe(0.01)
        snap = t.snapshot()
        assert snap["burn_rate"] == 0.0
        assert snap["error_budget_remaining"] == 1.0

    def test_gauges_ride_the_registry(self, tmp_path):
        from tpuframe.serve import SloTracker
        from tpuframe.track import telemetry as T

        T.configure(jsonl_dir=str(tmp_path), rank=0)
        try:
            t = SloTracker()
            t.observe(ok=False)
            reg = T.get_telemetry().registry
            assert reg.gauge("slo/burn_rate").value > 0
            assert reg.gauge("slo/error_budget").value == 0.0
            assert "tpuframe_slo_burn_rate" in reg.prometheus_text()
        finally:
            T.reset()

    def test_objectives_event_logged_at_construction(self, tmp_path):
        from tpuframe.serve import SloObjectives, SloTracker
        from tpuframe.track import telemetry as T

        T.configure(jsonl_dir=str(tmp_path), rank=0)
        try:
            SloTracker(SloObjectives(p99_ms=250.0, availability=0.99),
                       source="engine")
        finally:
            T.reset()
        evs = [e for e in _jsonl_events(tmp_path)
               if e["name"] == "slo/objectives"]
        assert evs and evs[0]["p99_ms"] == 250.0
        assert evs[0]["availability"] == 0.99
        assert evs[0]["source"] == "engine"

    def test_from_env_tolerant_vs_strict(self, monkeypatch):
        from tpuframe.serve import SloObjectives

        monkeypatch.setenv("TPUFRAME_SLO_P99_MS", "banana")
        assert SloObjectives.from_env().p99_ms == SloObjectives().p99_ms
        with pytest.raises(ValueError, match="banana"):
            SloObjectives.from_env(strict=True)

    def test_strict_range_validation(self, monkeypatch):
        from tpuframe.serve import SloObjectives

        monkeypatch.setenv("TPUFRAME_SLO_AVAILABILITY", "2.5")
        with pytest.raises(ValueError, match=r"\(0, 1\]"):
            SloObjectives.from_env(strict=True)

    def test_env_overrides_apply(self, monkeypatch):
        from tpuframe.serve import SloObjectives

        monkeypatch.setenv("TPUFRAME_SLO_P99_MS", "250")
        monkeypatch.setenv("TPUFRAME_SLO_AVAILABILITY", "0.99")
        obj = SloObjectives.from_env()
        assert obj.p99_ms == 250.0 and obj.availability == 0.99

    def test_slo_knobs_are_registered(self):
        from tpuframe.serve.admission import SERVE_ENV_DOMAINS, SERVE_ENV_VARS

        for var in ("TPUFRAME_SLO_P99_MS", "TPUFRAME_SLO_AVAILABILITY"):
            assert var in SERVE_ENV_VARS
            assert var in SERVE_ENV_DOMAINS
            assert SERVE_ENV_DOMAINS[var]["type"] == "float"


class TestDoctorSloSection:
    def test_section_shape(self, monkeypatch):
        from tpuframe.doctor import slo_section

        monkeypatch.setenv("TPUFRAME_SLO_P99_MS", "250")
        sec = slo_section()
        assert sec["objectives"]["p99_ms"] == 250.0
        assert sec["env"] == {"TPUFRAME_SLO_P99_MS": "250"}
        assert isinstance(sec["burn_rate"], float)
        assert isinstance(sec["error_budget_remaining"], float)
        assert sec["analyze"].startswith("python -m tpuframe.track analyze")

    def test_malformed_env_reported_not_crashed(self, monkeypatch):
        from tpuframe.doctor import slo_section

        monkeypatch.setenv("TPUFRAME_SLO_AVAILABILITY", "2.5")
        sec = slo_section()
        assert "2.5" in sec["objectives"]["error"]

    def test_report_includes_slo(self, monkeypatch):
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        from tpuframe.doctor import report

        assert "slo" in report(probe_timeout_s=60)


class TestAnalyzeServeTrace:
    def _traced_run(self, tmp_path, n=8):
        from tpuframe.serve import ServeEngine, ServingServer
        from tpuframe.serve.router import Router
        from tpuframe.track import telemetry as T

        T.configure(jsonl_dir=str(tmp_path), rank=0)
        try:
            fn, _ = _linear_model()
            eng = ServeEngine(fn, knobs=_knobs(), item_shape=(4, 3),
                              dtype="float32").start()
            srv = ServingServer(eng, port=0)
            router = Router([srv.url]).start()
            try:
                for i in range(n):
                    status, _, _ = _post_traced(router.url, _blob(i))
                    assert status == 200
            finally:
                router.close()
                srv.close()
                eng.stop()
        finally:
            T.reset()

    def test_skew_report_builds_serve_trace_block(self, tmp_path):
        import tpuframe.track.analyze as A

        self._traced_run(tmp_path)
        report = A.skew_report(A.load_dir(str(tmp_path)))
        tr = report["serve_trace"]
        assert tr and tr["version"] == A.SERVE_TRACE_VERSION
        assert tr["traces"] == 8
        for hop in ("route", "hop", "door", "queue_wait", "assemble",
                    "infer", "respond"):
            assert tr["hops"][hop]["count"] >= 8, hop
            assert tr["hops"][hop]["p50"] <= tr["hops"][hop]["p99"]
        assert tr["e2e"]["count"] == 8
        assert tr["retry_amplification"] >= 1.0
        assert 0.0 <= tr["queue_wait_share"] <= 1.0
        assert tr["slo"]["requests"] == 8
        # engine-side hops must tile inside the measured end-to-end time
        engine_side = sum(tr["hops"][h]["p50"]
                          for h in ("queue_wait", "assemble", "infer"))
        assert engine_side <= tr["e2e"]["p99"] * 1.5
        text = A.format_report(report)
        assert "request path" in text and "burn rate" in text

    def test_untraced_run_has_null_block(self, tmp_path):
        from tpuframe.serve import ServeEngine
        from tpuframe.track import telemetry as T
        import tpuframe.track.analyze as A

        fn, _ = _linear_model()
        T.configure(jsonl_dir=str(tmp_path), rank=0)
        try:
            eng = ServeEngine(fn, knobs=_knobs(), item_shape=(4, 3),
                              dtype="float32")
            with eng:
                for i in range(3):
                    eng.submit(np.random.RandomState(i).rand(4, 3)
                               .astype(np.float32)).result(timeout=10)
        finally:
            T.reset()
        report = A.skew_report(A.load_dir(str(tmp_path)))
        # requests flowed but nothing armed tracing: block absent, and
        # the contract keys still pin
        assert report["serve_trace"] is None
        assert set(report) == set(A.SKEW_REPORT_KEYS)

    def test_perfetto_trace_carries_trace_ids(self, tmp_path):
        import tpuframe.track.analyze as A

        self._traced_run(tmp_path, n=2)
        ranks = A.load_dir(str(tmp_path))
        doc = A.build_trace(ranks)
        blob = json.dumps(doc)
        # router-minted ids (16 hex chars) are searchable in the args
        route = [e for e in _jsonl_events(tmp_path)
                 if e["name"] == "fleet/route"]
        assert route and route[0]["trace"] in blob

    def test_load_dirs_stitches_colliding_ranks(self, tmp_path):
        from tpuframe.track import telemetry as T
        import tpuframe.track.analyze as A

        dirs = []
        for proc in range(2):  # two "processes", both rank 0
            d = tmp_path / f"proc{proc}"
            d.mkdir()
            T.configure(jsonl_dir=str(d), rank=0)
            try:
                T.get_telemetry().event("fleet/markup",
                                        replica=f"http://x:{proc}",
                                        reason="probe")
            finally:
                T.reset()
            dirs.append(str(d))
        ranks = A.load_dirs(dirs)
        assert [r.rank for r in ranks] == [0, 1000]
        # and the merged stream builds one timeline
        doc = A.build_trace(ranks)
        assert json.dumps(doc).count("http://x:") >= 2

    def test_baseline_gates_queue_wait_and_burn_rate(self, tmp_path):
        import tpuframe.track.analyze as A

        self._traced_run(tmp_path, n=6)
        report = A.skew_report(A.load_dir(str(tmp_path)))
        # force a nonzero current burn so the ratio is comparable
        report["serve_trace"]["slo"]["burn_rate"] = 5.0
        fast = tmp_path / "baseline_fast.json"
        fast.write_text(json.dumps({
            "backend": "cpu",
            "serve_trace": {
                "hops": {"queue_wait": {"p99": 1e-9}},
                "slo": {"burn_rate": 1.0},
            },
        }))
        diff = A.baseline_diff(report, str(fast), threshold=1.25,
                               backend="cpu")
        assert diff["regressions"]
        entry = diff["regressions"][0]
        assert entry["ratio_queue_wait_p99"] > 1.25
        assert entry["ratio_burn_rate"] == pytest.approx(5.0)
        text = A.format_report(report, diff)
        assert "queue_wait_p99" in text and "burn_rate" in text
        # an equal baseline does not regress
        same = tmp_path / "baseline_same.json"
        same.write_text(json.dumps({
            "backend": "cpu",
            "serve_trace": json.loads(json.dumps(report["serve_trace"])),
        }))
        ok = A.baseline_diff(report, str(same), threshold=1.25,
                             backend="cpu")
        assert not ok["regressions"]

    def test_traceless_baseline_is_incomparable_not_regressed(self, tmp_path):
        import tpuframe.track.analyze as A

        self._traced_run(tmp_path, n=4)
        report = A.skew_report(A.load_dir(str(tmp_path)))
        bare = tmp_path / "baseline_bare.json"
        bare.write_text(json.dumps({
            "backend": "cpu",
            "serve_latency": dict(report["serve_latency"]),
        }))
        diff = A.baseline_diff(report, str(bare), threshold=1.25,
                               backend="cpu")
        assert diff["baselines"], "serve_latency baseline must compare"
        assert "ratio_queue_wait_p99" not in diff["baselines"][0]
        assert "ratio_burn_rate" not in diff["baselines"][0]

"""Observability suite: tracing spans, per-block chain events, metrics
exposition round-trip, health freshness, and the fleet reporter.

Runs as its own CI gate (`pytest -m telemetry`).  The cross-node
contracts asserted here in-process (trace stitching over the announce
envelope, bit-identical `chain_getEvents` on replicas) are re-asserted
over real sockets by the 3-process testnets (tests/test_zz_sync_testnet,
test_zz_chaos_testnet)."""

import threading
import time

import pytest

from cess_tpu.chain import checkpoint
from cess_tpu.chain.types import Event
from cess_tpu.node import metrics as m
from cess_tpu.node import tracing
from cess_tpu.node.chain_spec import dev_sk, local_spec
from cess_tpu.node.rpc import RpcServer, rpc_call
from cess_tpu.node.service import Extrinsic, NodeService

pytestmark = pytest.mark.telemetry


# ------------------------------------------------------------ metrics


class TestExpositionRoundTrip:
    def build_registry(self):
        reg = m.Registry()
        c = m.Counter("t_requests", "requests served", reg)
        c.inc(41)
        c.inc()
        g = m.Gauge("t_depth", "queue depth", reg)
        g.set(2.5)
        lc = m.LabeledCounter("t_drops", "drops per peer", "peer", reg)
        lc.inc("10.0.0.1:99")
        lc.inc('we"ird\\peer\nname', 3)
        h = m.Histogram("t_lat", "latency", buckets=(0.1, 1.0, 10.0),
                        registry=reg)
        for v in (0.05, 0.5, 0.5, 5.0, 50.0):
            h.observe(v)
        return reg

    def test_round_trip_values(self):
        reg = self.build_registry()
        text = reg.render()
        fams = m.parse_exposition(text)
        assert fams["t_requests"].kind == "counter"
        assert fams["t_requests"].help == "requests served"
        assert fams["t_requests"].value() == 42
        assert fams["t_depth"].value() == 2.5
        assert fams["t_drops"].total() == 4
        # label escaping survives the round trip
        labels = {
            tuple(sorted(lbl.items()))
            for _, lbl, _ in fams["t_drops"].samples
        }
        assert (("peer", 'we"ird\\peer\nname'),) in labels

    def test_histogram_le_monotone_and_inf(self):
        reg = self.build_registry()
        fams = m.parse_exposition(reg.render())
        h = fams["t_lat"].histogram()
        les = [le for le, _ in h["buckets"]]
        cums = [c for _, c in h["buckets"]]
        assert les == sorted(les)
        assert cums == sorted(cums), "bucket counts must be cumulative"
        assert les[-1] == float("inf")
        assert cums[-1] == h["count"] == 5
        assert h["sum"] == pytest.approx(56.05)

    def test_help_and_type_lines_precede_samples(self):
        text = self.build_registry().render()
        lines = text.splitlines()
        i_help = lines.index("# HELP t_lat latency")
        i_type = lines.index("# TYPE t_lat histogram")
        first_sample = next(
            i for i, ln in enumerate(lines) if ln.startswith("t_lat_bucket")
        )
        assert i_help < i_type < first_sample

    def test_concurrent_render_is_torn_free(self):
        """Registry.render / Histogram.samples snapshot under locks:
        hammer observes + registrations from threads while rendering —
        no exceptions, and every rendered exposition is internally
        consistent (+Inf bucket == _count)."""
        reg = m.Registry()
        h = m.Histogram("t_c", "c", buckets=(0.5,), registry=reg)
        stop = threading.Event()
        errors = []

        def writer():
            i = 0
            while not stop.is_set():
                h.observe(i % 2)
                i += 1

        def registrar():
            i = 0
            while not stop.is_set():
                m.Counter(f"t_extra_{i}", "x", reg)
                i += 1
                time.sleep(0.001)

        threads = [threading.Thread(target=writer) for _ in range(3)]
        threads.append(threading.Thread(target=registrar))
        for t in threads:
            t.start()
        try:
            for _ in range(200):
                try:
                    fams = m.parse_exposition(reg.render())
                    hist = fams["t_c"].histogram()
                    cums = [c for _, c in hist["buckets"]]
                    assert cums == sorted(cums)
                    assert cums[-1] == hist["count"]
                except Exception as e:  # pragma: no cover
                    errors.append(e)
                    break
        finally:
            stop.set()
            for t in threads:
                t.join()
        assert not errors


# ------------------------------------------------------------ tracing


class TestTracer:
    def test_nesting_and_parenting(self):
        tr = tracing.Tracer(node="n1")
        with tr.span("root", tags={"k": 1}) as root:
            with tr.span("child") as child:
                assert child.trace_id == root.trace_id
                assert child.parent_id == root.span_id
            tr.event("point")
        spans = tr.spans(trace_id=root.trace_id)
        assert [s.name for s in spans] == ["child", "point", "root"]
        point = spans[1]
        assert point.parent_id == root.span_id

    def test_trace_id_propagation_overrides_mint(self):
        tr = tracing.Tracer(node="n2")
        with tr.span("import", trace="cafe0123deadbeef") as s:
            pass
        assert s.trace_id == "cafe0123deadbeef"
        assert tr.spans(trace_id="cafe0123deadbeef")

    def test_ring_is_bounded(self):
        tr = tracing.Tracer(node="n3", max_spans=16)
        for i in range(100):
            tr.event(f"e{i}")
        spans = tr.spans()
        assert len(spans) == 16
        assert spans[-1].name == "e99"

    def test_traces_summary_and_render(self):
        tr = tracing.Tracer(node="n4")
        with tr.span("block.author", tags={"number": 7}):
            with tr.span("author.execute"):
                pass
        summary = tr.traces()
        assert summary[-1]["root"] == "block.author"
        assert summary[-1]["spans"] == 2
        text = tracing.render_trace(tr.spans())
        assert "block.author" in text and "author.execute" in text
        # JSON round trip feeds the CLI's cross-node merge
        text2 = tracing.render_trace(
            [s.to_json() for s in tr.spans()])
        assert "author.execute" in text2


def _unlabelled(plane) -> dict:
    return {name: v for metric in plane.registry().metrics()
            for name, labels, v in metric.samples() if not labels}


class TestOverheadGuard:
    """The always-on instrumentation must be invisible next to the
    work it wraps (~0.4 s pairings, ms-scale folds): measured budget
    is generous for CI jitter but orders of magnitude below any
    instrumented stage."""

    def test_span_overhead_micros(self):
        tr = tracing.Tracer(node="bench")
        n = 2000
        t0 = time.perf_counter()
        for _ in range(n):
            with tr.span("x"):
                pass
        per = (time.perf_counter() - t0) / n
        assert per < 200e-6, f"span overhead {per * 1e6:.1f}µs"

    def test_histogram_observe_overhead_micros(self):
        h = m.Histogram("t_ovh", "x", registry=m.Registry())
        n = 20000
        t0 = time.perf_counter()
        for i in range(n):
            h.observe(0.001 * (i % 7))
        per = (time.perf_counter() - t0) / n
        assert per < 50e-6, f"observe overhead {per * 1e6:.1f}µs"

    def test_stage_overhead_micros(self):
        """A data-plane stage (profiler span + histogram observe) with
        the profiler off."""
        from cess_tpu.utils import stages

        plane = stages.StagePlane("test", ("x",), "t_stage_{}_seconds",
                                  "{}", {})
        n = 2000
        t0 = time.perf_counter()
        for _ in range(n):
            with plane.stage("x"):
                pass
        per = (time.perf_counter() - t0) / n
        assert _unlabelled(plane)["t_stage_x_seconds_count"] == n
        assert per < 100e-6, f"stage overhead {per * 1e6:.1f}µs"

    def test_stage_switch_off(self, monkeypatch):
        """CESS_STAGE_METRICS=0: no span, no observation, no count."""
        from cess_tpu.utils import stages

        opened = []
        monkeypatch.setattr(stages, "TraceAnnotation", opened.append)
        monkeypatch.setattr(stages, "ENABLED", False)
        plane = stages.StagePlane("test", ("x",), "t_off_{}_seconds", "{}",
                                  {"n": ("t_off_total", "n")})
        with plane.stage("x"):
            plane.count(n=3)
        assert opened == []
        samples = _unlabelled(plane)
        assert samples["t_off_x_seconds_count"] == 0
        assert samples["t_off_total"] == 0


# ------------------------------------------------------------ events


def make_pair():
    spec = local_spec()
    a = NodeService(spec, authority=spec.validators[0])
    b = NodeService(spec, authority=spec.validators[1])
    return spec, a, b


def author_block_with_extrinsic(spec, a):
    sk = dev_sk("alice", spec.chain_id)
    ext = Extrinsic(signer="alice", module="sminer", call="faucet_top_up",
                    args=[1000], nonce=a.nonces.get("alice", 0))
    ext.sign(sk, a.genesis)
    a.submit_extrinsic(ext)
    rec, slot = None, a.slot
    while rec is None:
        slot += 1
        rec = a.produce_block(slot=slot)
    return rec


class TestChainEvents:
    def test_lockstep_events_bit_identical(self):
        spec, a, b = make_pair()
        rec = author_block_with_extrinsic(spec, a)
        blk = a.block_store[a.head_hash]
        tid = a.block_traces[a.head_hash]
        assert b.handle_announce(blk.to_json(), trace=tid) == "imported"
        ea = a.events_of_block(rec.number)
        eb = b.events_of_block(rec.number)
        assert ea is not None and eb is not None
        assert ea[2] == eb[2], "event lists must be identical"
        assert ea[3] == eb[3], "event digests must be bit-identical"
        assert any(e.pallet == "sminer" for e in ea[2])
        # events are OUTSIDE the consensus state hash but replicas
        # still agree on it
        assert a.state_hash() == b.state_hash()

    def test_events_not_in_state_hash(self):
        spec, a, _ = make_pair()
        h0 = a.state_hash()
        a.rt.state.deposit_event("test", "Noise", x=1)
        assert a.state_hash() == h0
        blob = a.export_state()
        _, data = checkpoint.decode_blob(blob)
        assert "events" not in data["state"]

    def test_event_ring_bounded_and_sink_trimmed(self):
        spec, a, _ = make_pair()
        from cess_tpu.node import service as svc

        rec = author_block_with_extrinsic(spec, a)
        assert len(a.events_by_block) <= svc.EVENT_RING_BLOCKS
        # sink trim: overfill and commit one more block
        a.rt.state.events.extend(
            Event.of("test", "Pad", i=i) for i in range(svc.EVENT_SINK_MAX)
        )
        slot, rec = a.slot, None
        while rec is None:
            slot += 1
            rec = a.produce_block(slot=slot)
        assert len(a.rt.state.events) <= svc.EVENT_SINK_MAX

    def test_trace_stitches_author_and_importer(self):
        spec, a, b = make_pair()
        author_block_with_extrinsic(spec, a)
        blk = a.block_store[a.head_hash]
        tid = a.block_traces[a.head_hash]
        b.handle_announce(blk.to_json(), trace=tid)
        a_names = {s.name for s in a.tracer.spans(trace_id=tid)}
        b_names = {s.name for s in b.tracer.spans(trace_id=tid)}
        assert "block.author" in a_names
        assert {"block.import", "import.sig_batch",
                "import.execute"} <= b_names
        # one stitched tree renders from the merged span sets
        merged = (a.tracer.spans(trace_id=tid)
                  + b.tracer.spans(trace_id=tid))
        text = tracing.render_trace(merged)
        assert "block.author" in text and "block.import" in text

    def test_checkpoint_v4_blob_migrates_events_away(self):
        """A v4 blob (events still inside the state payload) restores
        into this build with an empty sink and the same state hash on
        every replica."""
        spec, a, _ = make_pair()
        author_block_with_extrinsic(spec, a)
        version, data = checkpoint.decode_blob(a.export_state())
        assert version == checkpoint.FORMAT_VERSION == 6
        data["state"]["events"] = [Event.of("legacy", "E", i=1)]
        out = []
        checkpoint._canon(data, out)
        v4 = checkpoint.MAGIC + (4).to_bytes(2, "big") + b"".join(out)
        fresh = NodeService(spec, authority=spec.validators[0])
        checkpoint.restore(fresh.rt, v4)
        # the legacy blob's event list is dropped by the migration —
        # only the fresh construction's genesis events remain
        assert Event.of("legacy", "E", i=1) not in fresh.rt.state.events
        again = NodeService(spec, authority=spec.validators[0])
        checkpoint.restore(again.rt, v4)
        assert (checkpoint.state_hash(fresh.rt)
                == checkpoint.state_hash(again.rt))


# ------------------------------------------------------------ rpc + fleet


class TestRpcSurface:
    @pytest.fixture()
    def pair_with_server(self):
        spec, a, b = make_pair()
        rec = author_block_with_extrinsic(spec, a)
        blk = a.block_store[a.head_hash]
        tid = a.block_traces[a.head_hash]
        b.handle_announce(blk.to_json(), trace=tid)
        server = RpcServer(b, port=0)
        server.start()
        try:
            yield spec, a, b, rec, tid, server
        finally:
            server.stop()

    def test_chain_get_events_and_digest(self, pair_with_server):
        spec, a, b, rec, tid, server = pair_with_server
        got = rpc_call(server.host, server.port, "chain_getEvents",
                       [rec.number])
        assert got["number"] == rec.number
        assert got["digest"] == a.events_of_block(rec.number)[3]
        assert any(e["pallet"] == "sminer" for e in got["events"])
        # by hash too
        got2 = rpc_call(server.host, server.port, "chain_getEvents",
                        [got["blockHash"]])
        assert got2 == got

    def test_system_traces_by_block_number(self, pair_with_server):
        spec, a, b, rec, tid, server = pair_with_server
        got = rpc_call(server.host, server.port, "system_traces",
                       [str(rec.number)])
        assert got["traceId"] == tid
        names = {s["name"] for s in got["spans"]}
        assert "block.import" in names
        summary = rpc_call(server.host, server.port, "system_traces", [])
        assert any(t["traceId"] == tid for t in summary["traces"])

    def test_system_health_fields(self, pair_with_server):
        spec, a, b, rec, tid, server = pair_with_server
        health = rpc_call(server.host, server.port, "system_health", [])
        for key in ("finalityLag", "bestBlock", "txPoolSize",
                    "peersSeen", "gossipDropped"):
            assert key in health
        assert health["bestBlock"] == rec.number
        assert health["finalityLag"] == rec.number - b.finalized_number

    def test_system_metrics_includes_proof_registry(self, pair_with_server):
        spec, a, b, rec, tid, server = pair_with_server
        text = rpc_call(server.host, server.port, "system_metrics", [])
        fams = m.parse_exposition(text)
        assert "cess_import_execute_seconds" in fams
        assert fams["cess_import_execute_seconds"].histogram()["count"] >= 1
        # the process-wide proof-stage registry is merged in
        assert "cess_proofs_verified" in fams

    def test_metric_help_lint(self, pair_with_server):
        spec, a, b, rec, tid, server = pair_with_server
        from cess_tpu.proof.xla_backend import proof_stage_registry

        for reg in (a.registry, b.registry, proof_stage_registry()):
            for metric in reg.metrics():
                assert metric.help, f"{metric.name} has no help text"


class TestFleetReporter:
    def test_report_from_live_pair(self):
        import os
        import sys

        sys.path.insert(0, os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        from tools.telemetry_report import FleetCollector, to_markdown

        spec, a, b = make_pair()
        sa, sb = RpcServer(a, port=0), RpcServer(b, port=0)
        sa.start()
        sb.start()
        try:
            collector = FleetCollector(
                [("127.0.0.1", sa.port), ("127.0.0.1", sb.port)])
            collector.sample()
            for _ in range(3):
                rec = author_block_with_extrinsic(spec, a)
                blk = a.block_store[a.head_hash]
                b.handle_announce(
                    blk.to_json(), trace=a.block_traces[a.head_hash])
                collector.sample()
            report = collector.report(elapsed_s=10.0)
        finally:
            sa.stop()
            sb.stop()
        fleet = report["fleet"]
        assert fleet["blocks_per_s"] > 0
        assert fleet["extrinsics_per_s"] > 0
        assert "finality_lag_p50" in fleet
        assert "finality_lag_p95" in fleet
        # the author's trace is stitched across both nodes
        assert fleet["stitched_traces"] >= 1
        importer = report["per_node"][f"127.0.0.1:{sb.port}"]
        assert importer["importStages"]["execute"]["count"] >= 3
        md = to_markdown(report)
        assert "blocks/s" in md and "import stage" in md

    def test_report_survives_dead_node(self):
        """One node of the fleet dies mid-window: the report must
        still build, mark that node unreachable, and keep totals over
        the survivors (regression: a dead node used to raise out of
        report() and abort the whole artifact)."""
        import os
        import socket
        import sys

        sys.path.insert(0, os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        from tools.telemetry_report import FleetCollector, to_markdown

        # reserve a port that is guaranteed closed during the test
        probe = socket.socket()
        probe.bind(("127.0.0.1", 0))
        dead_port = probe.getsockname()[1]
        probe.close()

        spec, a, _b = make_pair()
        sa = RpcServer(a, port=0)
        sa.start()
        try:
            collector = FleetCollector(
                [("127.0.0.1", sa.port), ("127.0.0.1", dead_port)],
                timeout=1.0)
            collector.sample()
            author_block_with_extrinsic(spec, a)
            collector.sample()
            report = collector.report(elapsed_s=5.0)
        finally:
            sa.stop()
        assert report["unreachable_nodes"] == 1
        live = report["per_node"][f"127.0.0.1:{sa.port}"]
        dead = report["per_node"][f"127.0.0.1:{dead_port}"]
        assert not live["unreachable"]
        assert dead["unreachable"]
        assert dead["samples"] == 0
        # survivor totals still computed
        assert live["blocksProduced"] >= 1
        assert report["fleet"]["blocks_per_s"] >= 0
        md = to_markdown(report)
        assert "UNREACHABLE" in md and "survivors" in md


class TestProofStageMetrics:
    def test_always_on_stage_histograms(self):
        from cess_tpu.ops import podr2
        from cess_tpu.ops.podr2 import Challenge, Podr2Params, keygen, \
            tag_fragment
        from cess_tpu.proof import XlaBackend
        from cess_tpu.proof.xla_backend import proof_stage_registry

        params = Podr2Params(n=8, s=4)
        sk, pk = keygen(b"telemetry-tee")
        name = b"telemetry-frag"
        data = bytes(i % 256 for i in range(params.fragment_bytes))
        tags = tag_fragment(sk, name, data, params)
        indices = (0, 3, 6)
        ch = Challenge(
            indices=indices,
            randoms=tuple(
                bytes([i]).ljust(20, b"\x11") for i in indices),
        )
        proof = podr2.prove(tags, data, ch, params)

        reg = proof_stage_registry()
        before = {
            fam.name: fam.histogram()["count"]
            for fam in (
                m.parse_exposition(reg.render()).values()
            ) if fam.kind == "histogram"
        }
        backend = XlaBackend(fused=False, device_h2c=False)
        assert backend.verify_batch(
            pk, [(name, ch, proof)], b"seed", params) == [True]
        fams = m.parse_exposition(reg.render())
        for stage in ("host_prep", "sigma_fold", "chunk_program",
                      "pairing"):
            fam = fams[f"cess_proof_stage_{stage}_seconds"]
            assert (fam.histogram()["count"]
                    > before.get(fam.name, 0)), stage
        assert fams["cess_proofs_verified"].value() >= 1
        assert fams["cess_proof_verify_seconds_total"].value() > 0

"""stf.monitoring tests: metric cells, sampler buckets, concurrent
increments, export round-trips, tracing (ISSUE 2 tentpole)."""

import json
import threading
import uuid

import pytest

from simple_tensorflow_tpu.platform import monitoring


def _name(suffix):
    # the registry is process-global: every test gets fresh family names
    return f"/test/{uuid.uuid4().hex[:8]}/{suffix}"


class TestCounter:
    def test_unlabeled_cell(self):
        c = monitoring.Counter(_name("runs"), "desc")
        assert c.get_cell().value() == 0
        c.get_cell().increase_by(1)
        c.get_cell().increase_by(4)
        assert c.get_cell().value() == 5

    def test_labeled_cells_are_independent(self):
        c = monitoring.Counter(_name("miss"), "desc", "reason")
        c.get_cell("a").increase_by(2)
        c.get_cell("b").increase_by(3)
        assert c.get_cell("a").value() == 2
        assert c.get_cell("b").value() == 3

    def test_wrong_label_arity(self):
        c = monitoring.Counter(_name("l"), "desc", "reason")
        with pytest.raises(ValueError, match="label"):
            c.get_cell()
        with pytest.raises(ValueError, match="label"):
            c.get_cell("a", "b")

    def test_counter_cannot_decrease(self):
        c = monitoring.Counter(_name("dec"), "desc")
        with pytest.raises(ValueError, match="increase"):
            c.get_cell().increase_by(-1)

    def test_duplicate_same_shape_adopts_cells(self):
        name = _name("dup")
        a = monitoring.Counter(name, "desc")
        a.get_cell().increase_by(7)
        b = monitoring.Counter(name, "desc")
        assert b.get_cell().value() == 7

    def test_duplicate_different_shape_raises(self):
        name = _name("clash")
        monitoring.Counter(name, "desc")
        with pytest.raises(ValueError, match="already registered"):
            monitoring.IntGauge(name, "desc")
        with pytest.raises(ValueError, match="already registered"):
            monitoring.Counter(name, "desc", "extra_label")

    def test_duplicate_sampler_with_different_buckets_raises(self):
        name = _name("hclash")
        monitoring.Sampler(name, monitoring.ExponentialBuckets(1.0, 2.0, 4),
                           "desc")
        # identical buckets adopt; different edges must NOT mix series
        monitoring.Sampler(name, monitoring.ExponentialBuckets(1.0, 2.0, 4),
                           "desc")
        with pytest.raises(ValueError, match="already registered"):
            monitoring.Sampler(name,
                               monitoring.ExponentialBuckets(1.0, 10.0, 4),
                               "desc")

    def test_concurrent_increments(self):
        c = monitoring.Counter(_name("conc"), "desc")
        cell = c.get_cell()

        def worker():
            for _ in range(1000):
                cell.increase_by(1)

        threads = [threading.Thread(target=worker) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert cell.value() == 8000


class TestGauges:
    def test_int_gauge(self):
        g = monitoring.IntGauge(_name("g"), "desc")
        assert g.get_cell().value() == 0
        g.get_cell().set(42)
        assert g.get_cell().value() == 42

    def test_string_gauge(self):
        g = monitoring.StringGauge(_name("s"), "desc", "which")
        g.get_cell("v").set("hello")
        assert g.get_cell("v").value() == "hello"

    def test_bool_gauge(self):
        g = monitoring.BoolGauge(_name("b"), "desc")
        g.get_cell().set(True)
        assert g.get_cell().value() is True


class TestSampler:
    def test_exponential_bucket_boundaries(self):
        b = monitoring.ExponentialBuckets(1.0, 2.0, 4)
        assert b.boundaries == [1.0, 2.0, 4.0, 8.0]

    def test_exponential_bucket_validation(self):
        with pytest.raises(ValueError):
            monitoring.ExponentialBuckets(0.0, 2.0, 4)
        with pytest.raises(ValueError):
            monitoring.ExponentialBuckets(1.0, 1.0, 4)
        with pytest.raises(ValueError):
            monitoring.ExplicitBuckets([1.0, 1.0])

    def test_samples_land_in_buckets(self):
        s = monitoring.Sampler(_name("h"),
                               monitoring.ExponentialBuckets(1.0, 10.0, 3),
                               "desc")
        cell = s.get_cell()
        # edges 1, 10, 100, +inf -> buckets (-inf,1], (1,10], (10,100], rest
        for v in (0.5, 5.0, 50.0, 500.0, 5000.0):
            cell.add(v)
        snap = cell.value()
        assert snap["count"] == 5
        assert snap["sum"] == pytest.approx(5555.5)
        counts = [c for _, c in snap["buckets"]]
        assert counts == [1, 1, 1, 2]
        assert snap["buckets"][-1][0] == float("inf")
        assert snap["min"] == 0.5 and snap["max"] == 5000.0

    def test_boundary_exact_sample_is_le_inclusive(self):
        s = monitoring.Sampler(_name("edge"),
                               monitoring.ExplicitBuckets([1.0, 2.0]),
                               "desc")
        cell = s.get_cell()
        cell.add(1.0)  # == first edge: counts at-or-below it (le)
        counts = [c for _, c in cell.value()["buckets"]]
        assert counts == [1, 0, 0]

    def test_labeled_sampler(self):
        s = monitoring.Sampler(_name("hp"),
                               monitoring.ExponentialBuckets(1e-6, 4.0, 8),
                               "desc", "phase")
        s.get_cell("prune").add(1e-5)
        s.get_cell("optimize").add(1e-4)
        assert s.get_cell("prune").value()["count"] == 1
        assert s.get_cell("optimize").value()["count"] == 1


class TestPercentileSampler:
    def test_percentiles(self):
        p = monitoring.PercentileSampler(_name("p"), "desc",
                                         percentiles=(50.0, 90.0))
        cell = p.get_cell()
        for v in range(1, 101):
            cell.add(float(v))
        snap = cell.value()
        assert snap["count"] == 100
        assert snap["percentiles"][50.0] == pytest.approx(50.0, abs=2)
        assert snap["percentiles"][90.0] == pytest.approx(90.0, abs=2)

    def test_labels_are_positional_like_other_families(self):
        # PercentileSampler(name, desc, "label") must bind "label" as a
        # label name, never as the percentile list
        p = monitoring.PercentileSampler(_name("plbl"), "desc", "phase")
        assert p.label_names == ("phase",)
        p.get_cell("compile").add(1.0)
        assert p.get_cell("compile").value()["count"] == 1

    def test_ring_buffer_bounds_memory(self):
        p = monitoring.PercentileSampler(_name("ring"), "desc",
                                         percentiles=(50.0,), max_samples=16)
        cell = p.get_cell()
        for v in range(1000):
            cell.add(float(v))
        snap = cell.value()
        assert snap["count"] == 1000
        # only the most recent 16 samples are retained
        assert snap["percentiles"][50.0] >= 984


class TestExport:
    def test_export_round_trip(self):
        name = _name("exp")
        c = monitoring.Counter(name, "my description", "kind")
        c.get_cell("x").increase_by(3)
        exp = monitoring.export()
        assert exp[name]["type"] == "Counter"
        assert exp[name]["description"] == "my description"
        assert exp[name]["labels"] == ["kind"]
        assert exp[name]["cells"]["x"] == 3
        # to_json parses back and still contains the cell
        parsed = json.loads(monitoring.to_json())
        assert parsed[name]["cells"]["x"] == 3

    def test_prometheus_output(self):
        cname = _name("prom")
        c = monitoring.Counter(cname, "prom desc", "reason")
        c.get_cell("new").increase_by(2)
        sname = _name("promh")
        s = monitoring.Sampler(sname,
                               monitoring.ExponentialBuckets(1.0, 2.0, 2),
                               "hist desc")
        s.get_cell().add(1.5)
        text = monitoring.to_prometheus()
        pc = monitoring._prom_name(cname)
        ps = monitoring._prom_name(sname)
        assert f"# TYPE {pc} counter" in text
        assert f'{pc}{{reason="new"}} 2' in text
        assert f"# TYPE {ps} histogram" in text
        assert f"{ps}_count 1" in text

    def test_pipe_in_label_values_does_not_collide(self):
        name = _name("pipe")
        c = monitoring.Counter(name, "d", "a", "b")
        c.get_cell("x|y", "z").increase_by(1)
        c.get_cell("x", "y|z").increase_by(2)
        cells = monitoring.export()[name]["cells"]
        assert len(cells) == 2 and sorted(cells.values()) == [1, 2]
        # prometheus splits the escaped key back into the right values
        text = monitoring.to_prometheus()
        pn = monitoring._prom_name(name)
        assert f'{pn}{{a="x|y",b="z"}} 1' in text
        assert f'{pn}{{a="x",b="y|z"}} 2' in text

    def test_prometheus_escapes_label_values(self):
        name = _name("esc")
        c = monitoring.Counter(name, "line1\nline2", "path")
        c.get_cell('a"b\\c\nd').increase_by(1)
        text = monitoring.to_prometheus()
        pn = monitoring._prom_name(name)
        assert f'{pn}{{path="a\\"b\\\\c\\nd"}} 1' in text
        assert f"# HELP {pn} line1\\nline2" in text
        # no raw newline leaks into the middle of a series line
        for line in text.splitlines():
            assert not line.endswith('\\')

    def test_prometheus_histogram_buckets_are_cumulative_with_inf(self):
        # satellite (ISSUE 8): the native histogram contract —
        # _bucket series are CUMULATIVE, end at le="+Inf", and the
        # +Inf bucket equals _count
        name = _name("cum")
        s = monitoring.Sampler(name,
                               monitoring.ExplicitBuckets([1.0, 10.0]),
                               "d")
        cell = s.get_cell()
        for v in (0.5, 0.7, 5.0, 50.0):
            cell.add(v)
        text = monitoring.to_prometheus()
        pn = monitoring._prom_name(name)
        assert f'{pn}_bucket{{le="1.0"}} 2' in text
        assert f'{pn}_bucket{{le="10.0"}} 3' in text
        assert f'{pn}_bucket{{le="+Inf"}} 4' in text
        assert f"{pn}_count 4" in text
        from prom_format import validate_prometheus_text

        validate_prometheus_text(text)

    def test_prometheus_empty_label_value_keeps_pair(self):
        # a cell whose label VALUE is "" must still emit the label pair
        # (the old export()-keyed path dropped it, colliding with an
        # unlabeled series)
        name = _name("emptyv")
        c = monitoring.Counter(name, "d", "shard")
        c.get_cell("").increase_by(3)
        c.get_cell("a").increase_by(4)
        text = monitoring.to_prometheus()
        pn = monitoring._prom_name(name)
        assert f'{pn}{{shard=""}} 3' in text
        assert f'{pn}{{shard="a"}} 4' in text

    def test_prometheus_name_sanitization(self):
        # /stf/... path style -> underscores; leading digit guarded
        assert monitoring._prom_name(
            "/stf/session/executable_cache/misses") \
            == "stf_session_executable_cache_misses"
        assert monitoring._prom_name("/9lives/x") == "_9lives_x"
        assert monitoring._prom_name("///") == "_"
        name = _name("weird")
        c = monitoring.Counter(name + "/with-dash.dot", "d")
        c.get_cell().increase_by(1)
        from prom_format import validate_prometheus_text

        validate_prometheus_text(monitoring.to_prometheus())

    def test_prometheus_help_escapes_backslash(self):
        name = _name("bs")
        monitoring.Counter(name, "path C:\\tmp\nnext", )
        text = monitoring.to_prometheus()
        pn = monitoring._prom_name(name)
        assert f"# HELP {pn} path C:\\\\tmp\\nnext" in text

    def test_prometheus_summary_quantiles(self):
        name = _name("sq")
        p = monitoring.PercentileSampler(name, "d",
                                         percentiles=(50.0, 99.0))
        cell = p.get_cell()
        for v in range(1, 101):
            cell.add(float(v))
        text = monitoring.to_prometheus()
        pn = monitoring._prom_name(name)
        assert f"# TYPE {pn} summary" in text
        assert f'{pn}{{quantile="0.5"}}' in text
        assert f'{pn}{{quantile="0.99"}}' in text
        assert f"{pn}_count 100" in text

    def test_prometheus_whole_registry_validates(self):
        # whatever this process has registered so far must render as a
        # well-formed exposition (torn lines, raw newlines, bad label
        # blocks all fail the validator)
        from prom_format import validate_prometheus_text

        series = validate_prometheus_text(monitoring.to_prometheus())
        assert series  # the library's own /stf/ metrics are present

    def test_to_json_is_strict_json(self):
        name = _name("strict")
        s = monitoring.Sampler(name,
                               monitoring.ExponentialBuckets(1.0, 2.0, 2),
                               "d")
        s.get_cell().add(1.5)
        parsed = json.loads(monitoring.to_json())  # RFC-8259 parse
        edges = [e for e, _ in parsed[name]["cells"][""]["buckets"]]
        assert edges[-1] == "inf"

    def test_unregister(self):
        name = _name("gone")
        monitoring.Counter(name, "d")
        assert monitoring.get_metric(name) is not None
        monitoring.unregister(name)
        assert monitoring.get_metric(name) is None


class TestTracing:
    def test_traceme_without_collection_is_noop(self):
        with monitoring.traceme("nothing", k=1):
            pass  # no sink installed: must not raise or record

    def test_traceme_records_into_active_buffer(self):
        with monitoring.trace_collection() as buf:
            with monitoring.traceme("phase_a", detail="x"):
                pass
            with monitoring.traceme("phase_b"):
                pass
        spans = buf.drain()
        names = [s["name"] for s in spans]
        assert names == ["phase_a", "phase_b"]
        assert spans[0]["meta"] == {"detail": "x"}
        assert all(s["dur_s"] >= 0 for s in spans)
        # buffer detached after the with block
        with monitoring.traceme("after"):
            pass
        assert len(buf) == 0

    def test_nested_collections_both_record(self):
        with monitoring.trace_collection() as outer:
            with monitoring.trace_collection() as inner:
                with monitoring.traceme("span"):
                    pass
            assert len(inner) == 1
            assert len(outer) == 1

    def test_a_layered_name_reaches_a_collection_as_its_phase(self):
        with monitoring.trace_collection() as buf:
            with monitoring.traceme("session/device_execute", n=3) as sp:
                sp.set_meta(late=True)
            with monitoring.traceme("optimizer/graph_pass:a/b"):
                pass
        first, second = buf.drain()
        assert first["name"] == "device_execute"
        assert first["meta"] == {"n": 3, "late": True}
        assert first["dur_s"] >= 0
        # the layer is the text before the FIRST slash
        assert second["name"] == "graph_pass:a/b"

    def test_tracing_active(self):
        assert not monitoring.tracing_active()
        with monitoring.trace_collection():
            assert monitoring.tracing_active()
        assert not monitoring.tracing_active()

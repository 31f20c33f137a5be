"""Metrics-name lint: every registered family follows the
``{component}_{noun}[_{unit}][_total]`` convention and is documented in
docs/OBSERVABILITY.md.

Undocumented or misnamed telemetry rots fastest: a dashboard built on a
family nobody wrote down breaks silently on the next rename. This test
imports every metric-defining module (so the registry is fully
populated), then walks ``metrics.families()`` and fails on any family
that (a) is not snake_case, (b) has the wrong suffix discipline for its
kind, (c) starts with an unknown component, or (d) has no row in the
docs page.
"""

from __future__ import annotations

import importlib
import os
import re

import pytest

# Every module that registers a metric family. A new metric in a new
# module must be added here (the scrape tests would miss it silently
# otherwise) — grep for `metrics.counter|gauge|histogram` when in doubt.
METRIC_MODULES = (
    "dragonfly2_tpu.pkg.bufpool",
    "dragonfly2_tpu.pkg.chaos",
    "dragonfly2_tpu.pkg.flight",
    "dragonfly2_tpu.pkg.fleet",
    "dragonfly2_tpu.pkg.cluster",
    "dragonfly2_tpu.pkg.prof",
    "dragonfly2_tpu.pkg.slo",
    "dragonfly2_tpu.pkg.tracing",
    "dragonfly2_tpu.daemon.proxy",
    "dragonfly2_tpu.daemon.upload",
    "dragonfly2_tpu.daemon.objectstorage",
    "dragonfly2_tpu.daemon.peer.conductor",
    "dragonfly2_tpu.daemon.peer.task_manager",
    "dragonfly2_tpu.daemon.peer.device_sink",
    "dragonfly2_tpu.client.device",
    "dragonfly2_tpu.ops.bitview",
    "dragonfly2_tpu.scheduler.service",
    "dragonfly2_tpu.manager.client",
    "dragonfly2_tpu.proto.reportcodec",
    "dragonfly2_tpu.qos.wfq",
    "dragonfly2_tpu.qos.admission",
    "dragonfly2_tpu.delta.chunker",
    "dragonfly2_tpu.delta.manifest",
    "dragonfly2_tpu.delta.resolver",
    "dragonfly2_tpu.storage.io_ring",
    "dragonfly2_tpu.storage.local_store",
    "dragonfly2_tpu.dataset.loader",
    "dragonfly2_tpu.dataset.shard_reader",
    "dragonfly2_tpu.dataset.tar_index",
    "dragonfly2_tpu.dataset.device_feed",
)

# The documented component vocabulary (docs/OBSERVABILITY.md "Metric
# families"). Adding a component means documenting it there first.
COMPONENTS = ("bufpool", "chaos", "dataset", "delta", "device_sharded",
              "device_sink", "device_swap", "device_views", "fleet", "manager",
              "objectstorage", "peer", "proxy", "qos", "runtime", "scheduler",
              "storage", "store", "tracing", "upload")

# Histogram families must name their unit; counters use _total; gauges
# may end in a unit but never _total. "pieces" is a unit here: batch-size
# histograms (scheduler_ingest_batch_pieces) count pieces, not time/bytes.
UNITS = ("seconds", "bytes", "ms", "pieces")

DOCS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "docs", "OBSERVABILITY.md")

SNAKE = re.compile(r"^[a-z][a-z0-9]*(_[a-z0-9]+)*$")


@pytest.fixture(scope="module")
def all_families():
    for mod in METRIC_MODULES:
        importlib.import_module(mod)
    from dragonfly2_tpu.pkg import metrics

    # The registry is the process's: another test file that ran in this
    # worker first (tests/test_pkg_kernel.py) leaves its own ``test_``
    # family in it, and which files share a worker changes from run to run.
    fams = [f for f in metrics.families()
            if not f["name"].startswith("test_")]
    assert len(fams) >= 30, "registry suspiciously small — import miss?"
    return fams


def test_names_are_snake_case(all_families):
    bad = [f["name"] for f in all_families if not SNAKE.match(f["name"])]
    assert not bad, f"non-snake_case metric names: {bad}"


def test_component_prefix_is_documented(all_families):
    bad = [f["name"] for f in all_families
           if not any(f["name"].startswith(c + "_") for c in COMPONENTS)]
    assert not bad, (
        f"metric families outside the documented component vocabulary "
        f"{COMPONENTS}: {bad} — extend docs/OBSERVABILITY.md first")


def test_suffix_discipline_per_kind(all_families):
    bad = []
    for f in all_families:
        name, kind = f["name"], f["kind"]
        if kind == "counter" and not name.endswith("_total"):
            bad.append((name, "counter must end in _total"))
        elif kind == "gauge" and name.endswith("_total"):
            bad.append((name, "gauge must not end in _total"))
        elif kind == "histogram" and not name.endswith(
                tuple(f"_{u}" for u in UNITS)):
            bad.append((name, f"histogram must end in a unit {UNITS}"))
    assert not bad, f"suffix convention violations: {bad}"


def test_every_family_documented(all_families):
    with open(DOCS) as f:
        doc = f.read()
    undocumented = [f["name"] for f in all_families
                    if f"`{f['name']}`" not in doc]
    assert not undocumented, (
        f"metric families missing from docs/OBSERVABILITY.md: "
        f"{undocumented} — every family needs a table row there")


def test_the_views_counter_says_which_form_cut_the_tensor(all_families):
    """``device_views_tensors_total{form}``: ``rows`` for the kernel that
    writes a view once, ``flat`` for the general loops; both documented."""
    by_name = {f["name"]: f for f in all_families}
    assert by_name["device_views_tensors_total"]["labels"] == ("form",)
    assert by_name["device_views_dispatches_total"]["labels"] == ()
    with open(DOCS) as f:
        row = next(line for line in f
                   if line.startswith("| `device_views_tensors_total`"))
    assert "| `form` |" in row and "`rows`" in row and "`flat`" in row


def test_every_family_has_help_text(all_families):
    thin = [f["name"] for f in all_families if len(f["doc"]) < 10]
    assert not thin, f"metric families with no real help text: {thin}"


# --------------------------------------------------------------------- #
# Exposition round trips (OpenMetrics conformance satellite)
# --------------------------------------------------------------------- #

def test_prometheus_exposition_round_trips_families(all_families):
    """Strict-parse our own classic exposition and cross-check every
    registered family appears with # HELP/# TYPE and the right kind —
    a silent serialization bug would otherwise only surface when an
    external scraper chokes."""
    from prometheus_client import parser

    from dragonfly2_tpu.pkg import metrics

    text = metrics.render()[0].decode()
    assert "# HELP" in text and "# TYPE" in text
    parsed = {f.name: f for f in parser.text_string_to_metric_families(text)}
    for fam in all_families:
        full = f"dragonfly_tpu_{fam['name']}"
        # The parser names counters without the _total suffix.
        key = full[:-len("_total")] if fam["kind"] == "counter" else full
        assert key in parsed, f"{full} missing from exposition"
        assert parsed[key].type == fam["kind"], full
        assert parsed[key].documentation, full


def test_openmetrics_round_trip_and_label_escaping():
    """The OpenMetrics content negotiation: render with the OpenMetrics
    Accept type, parse with the STRICT OpenMetrics parser (it rejects
    missing # EOF, bad escapes, suffix violations), and recover a label
    value containing every character class the escaping rules cover —
    in an isolated registry so the process registry stays lint-clean."""
    from prometheus_client import CollectorRegistry, Counter
    from prometheus_client.openmetrics import parser as om_parser

    from dragonfly2_tpu.pkg import metrics

    reg = CollectorRegistry()
    c = Counter("scheduler_escape_probe", "Label escaping probe",
                ("note",), namespace="dragonfly_tpu", registry=reg)
    tricky = 'quote " backslash \\ newline \n tab \t end'
    c.labels(tricky).inc(3)

    body, ctype = metrics.render("application/openmetrics-text",
                                 registry=reg)
    assert "openmetrics" in ctype
    text = body.decode()
    assert text.rstrip().endswith("# EOF")
    fams = list(om_parser.text_string_to_metric_families(text))
    samples = [s for f in fams for s in f.samples
               if s.name == "dragonfly_tpu_scheduler_escape_probe_total"]
    assert samples, fams
    assert samples[0].labels["note"] == tricky
    assert samples[0].value == 3

    # The classic format negotiates too, and round-trips the same value.
    from prometheus_client import parser as classic_parser

    body, ctype = metrics.render("", registry=reg)
    assert "openmetrics" not in ctype
    fams = list(classic_parser.text_string_to_metric_families(
        body.decode()))
    samples = [s for f in fams for s in f.samples
               if s.name == "dragonfly_tpu_scheduler_escape_probe_total"]
    assert samples[0].labels["note"] == tricky


def test_metrics_endpoint_negotiates_openmetrics(run_async):
    import aiohttp

    from dragonfly2_tpu.pkg.metrics_server import MetricsServer

    async def body():
        srv = MetricsServer()
        port = await srv.serve("127.0.0.1", 0)
        try:
            async with aiohttp.ClientSession() as sess:
                headers = {"Accept":
                           "application/openmetrics-text; version=1.0.0"}
                async with sess.get(f"http://127.0.0.1:{port}/metrics",
                                    headers=headers) as r:
                    assert "openmetrics" in r.headers["Content-Type"]
                    text = await r.text()
                assert text.rstrip().endswith("# EOF")
                async with sess.get(
                        f"http://127.0.0.1:{port}/metrics") as r:
                    assert "openmetrics" not in r.headers["Content-Type"]
        finally:
            await srv.close()

    run_async(body(), timeout=60)


# --------------------------------------------------------------------- #
# Debug-route documentation lint (routes introspected, not hand-listed)
# --------------------------------------------------------------------- #

def test_every_debug_route_documented():
    """Every /debug route the MetricsServer registers must appear in
    docs/OBSERVABILITY.md. Routes come from MetricsServer.ROUTES — the
    same table serve() registers from — so an endpoint cannot ship
    undocumented and this list cannot rot."""
    from dragonfly2_tpu.pkg.metrics_server import MetricsServer

    routes = MetricsServer.debug_routes()
    assert "/debug/pod/{task_id}/timeline" in routes
    assert "/debug/slo" in routes
    with open(DOCS) as f:
        doc = f.read()
    missing = []
    for route in routes:
        needle = route.replace("{task_id}", "<task_id>")
        if needle not in doc:
            missing.append(route)
    assert not missing, (
        f"debug routes missing from docs/OBSERVABILITY.md: {missing}")

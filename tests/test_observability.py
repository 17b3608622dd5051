"""Live observability (PR 18): exemplar reservoirs on the SLO
histograms, OpenMetrics exposition with exemplars, SLO burn-rate
alerting at the flush boundary, the flight --list CLI, and the
opsplane HTTP endpoint — including the tier-1 smoke that boots the plane on an ephemeral port during a real
ContinuousServer run.
"""

import json
import re
import urllib.request

import jax
import pytest

from hpx_tpu.core import config_schema
from hpx_tpu.core.config import runtime_config
from hpx_tpu.models import transformer as tfm
from hpx_tpu.models.serving import ContinuousServer
from hpx_tpu.svc import exemplars, faultinject, flight, metrics, opsplane
from hpx_tpu.svc.metrics import HistogramCounter
from hpx_tpu.svc.slo_alerts import (
    DEFAULT_RULES,
    SloAlerts,
    SloRule,
    parse_rules,
)

CFG = tfm.TransformerConfig(vocab=64, d_model=32, n_heads=4, head_dim=8,
                            n_layers=2, d_ff=64)


@pytest.fixture(scope="module")
def params():
    return tfm.init_params(CFG, jax.random.PRNGKey(0))


@pytest.fixture()
def knobs():
    """Set config knobs for one test; restore each touched key to its
    declared schema default afterwards."""
    cfg = runtime_config()
    touched = []

    def set_(key, value):
        touched.append(key)
        cfg.set(key, value)

    yield set_
    defaults = config_schema.all_keys()
    for key in touched:
        d = defaults[key].default
        cfg.set(key, "" if d is None else d)


# ---------------------------------------------------------------------------
# exemplar reservoirs
# ---------------------------------------------------------------------------

def _record_seq(h, seq):
    for rid, v in seq:
        h.record(v, rid=rid)


def test_reservoir_deterministic_replacement():
    """Same record sequence in, same exemplars out — slot n%per_bucket,
    no RNG. Two independent hist+reservoir pairs agree exactly on
    (rid, value, bucket)."""
    seq = [(f"r{i}", v) for i, v in enumerate(
        [0.01, 0.5, 2.0, 0.02, 3.0, 2.5, 0.03, 4.0, 2.2, 3.3] * 5)]
    got = []
    for _ in range(2):
        h = HistogramCounter()
        ex = exemplars.attach(h, per_bucket=2, quantile=0.8, refresh=4)
        _record_seq(h, seq)
        got.append([(e["rid"], e["value"], e["bucket"])
                    for e in ex.exemplars()])
    assert got[0] == got[1]
    assert got[0]                        # something was captured


def test_reservoir_ring_keeps_newest_per_bucket():
    h = HistogramCounter()
    ex = exemplars.attach(h, per_bucket=2, quantile=0.0, refresh=1)
    # five offers to one bucket: ring of 2 keeps the last two, ordered
    # oldest->newest; newest_per_bucket picks the final one
    for i in range(5):
        h.record(1.0, rid=f"r{i}")
    idx = h._index(1.0)
    rids = [e["rid"] for e in ex.exemplars()]
    assert rids == ["r3", "r4"]
    assert ex.newest_per_bucket()[idx]["rid"] == "r4"
    assert ex.captured == 5 and ex.offered == 5


def test_reservoir_threshold_skips_below_tail():
    """With 20% of mass in the top bucket and quantile=0.9, the p90
    lands in the top bucket — low-bucket records are not tail samples
    and are not captured."""
    h = HistogramCounter()
    ex = exemplars.attach(h, per_bucket=4, quantile=0.9, refresh=1)
    for i in range(80):
        h.record(0.001, rid=f"lo{i}")
    for i in range(20):
        h.record(4.0, rid=f"hi{i}")
    before = ex.captured
    h.record(0.001, rid="late-lo")       # below the p90 bucket
    assert ex.captured == before
    h.record(4.0, rid="late-hi")         # tail bucket
    assert ex.captured == before + 1
    assert all(not e["rid"].startswith("late-lo")
               for e in ex.exemplars())


def test_attach_from_config_gate(knobs):
    h = HistogramCounter()
    assert exemplars.attach_from_config({"e2e": h}) == []
    assert h._ex is None                 # off by default: no reservoir
    knobs("hpx.obs.exemplars", "1")
    knobs("hpx.obs.exemplars_per_bucket", "2")
    knobs("hpx.obs.exemplar_quantile", "0.5")
    attached = exemplars.attach_from_config({"e2e": h})
    assert len(attached) == 1 and h._ex is attached[0]
    assert h._ex.per_bucket == 2 and h._ex.quantile == 0.5


def test_snapshot_embeds_exemplars_and_stays_mergeable():
    h = HistogramCounter()
    exemplars.attach(h, per_bucket=2, quantile=0.0, refresh=1)
    h.record(0.25, rid="req-9")
    snap = h.snapshot()
    assert snap["exemplars"][0]["rid"] == "req-9"
    # the extra key must not break the snapshot algebra
    h2 = HistogramCounter.from_snapshot(snap)
    assert h2.count == 1
    d = h.delta(snap)
    assert d["count"] == 0 and "exemplars" not in d
    bare = HistogramCounter()
    bare.record(1.0)
    assert "exemplars" not in bare.snapshot()


# ---------------------------------------------------------------------------
# OpenMetrics exposition
# ---------------------------------------------------------------------------

def test_exposition_negotiation():
    om, ct = metrics.negotiate_exposition(
        "application/openmetrics-text; version=1.0.0")
    assert om and ct == metrics.OPENMETRICS_CONTENT_TYPE
    for accept in (None, "", "text/plain", "*/*"):
        om, ct = metrics.negotiate_exposition(accept)
        assert not om and ct == metrics.PROM_CONTENT_TYPE


def test_prom_escape_edge_cases():
    assert metrics._prom_escape('a\\b"c\nd') == 'a\\\\b\\"c\\nd'
    assert metrics._prom_escape("plain#0") == "plain#0"   # no-op


def test_exposition_exact_text_both_formats():
    """The pinned wire format: default v0.0.4 output is byte-stable
    (no exemplars, no # EOF); OpenMetrics adds the exemplar clause on
    the tail bucket row and terminates with # EOF."""
    import hpx_tpu.svc.performance_counters as pc
    h = HistogramCounter()
    ex = exemplars.attach(h, per_bucket=1, quantile=0.0, refresh=1)
    h.record(0.25, rid="req-42")
    idx = h._index(0.25)
    ex._slots[idx][0]["ts"] = 1234.5     # pin wall time for exact text
    names = metrics.register_histogram(
        "serving", "latency/obs-test-s", h, "obs#0", quantiles=())
    try:
        le = h.bucket_upper(idx)
        pat = "/serving{locality#*/obs#0}/latency/obs-test-s"
        plain = metrics.render_prometheus(pattern=pat)
        om = metrics.render_prometheus(pattern=pat, openmetrics=True)
        metric = "hpx_serving_latency_obs_test_s"
        bucket = (f'{metric}_bucket{{le="{le:.9g}",locality="0",'
                  f'instance="obs#0"}} 1')
        assert plain == (
            f"# TYPE {metric} histogram\n"
            f"{bucket}\n"
            f'{metric}_bucket{{le="+Inf",locality="0",'
            f'instance="obs#0"}} 1\n'
            f"{metric}_sum{{locality=\"0\",instance=\"obs#0\"}} 0.25\n"
            f"{metric}_count{{locality=\"0\",instance=\"obs#0\"}} 1\n")
        assert om == (
            f"# TYPE {metric} histogram\n"
            f'{bucket} # {{rid="req-42"}} 0.25 1234.500\n'
            f'{metric}_bucket{{le="+Inf",locality="0",'
            f'instance="obs#0"}} 1\n'
            f"{metric}_sum{{locality=\"0\",instance=\"obs#0\"}} 0.25\n"
            f"{metric}_count{{locality=\"0\",instance=\"obs#0\"}} 1\n"
            "# EOF\n")
    finally:
        for n in names:
            pc.unregister_counter(n)


# ---------------------------------------------------------------------------
# SLO burn-rate alerting
# ---------------------------------------------------------------------------

def test_parse_rules():
    rules = parse_rules("e2e:1.0:0.95, decode_stall:0.25:0.99")
    assert rules == (SloRule("e2e", 1.0, 0.95),
                     SloRule("decode_stall", 0.25, 0.99))
    assert parse_rules("") == DEFAULT_RULES


def _scripted_burn_run():
    """One scripted incident against synthetic clocks: a long good
    history, a brief spike the slow window gates, a sustained
    regression that fires once, then recovery that clears."""
    h = HistogramCounter()
    a = SloAlerts({"e2e": h}, rules=(SloRule("e2e", 1.0, 0.9),),
                  fast_s=10.0, slow_s=60.0,
                  burn_fast=3.0, burn_slow=2.0, interval_s=0.0,
                  clock=lambda: 0.0)
    t = 0.0
    # 60s of healthy traffic: 2 good samples / 5s
    for _ in range(12):
        h.record(0.1)
        h.record(0.2)
        t += 5.0
        a.evaluate(t)
    assert a.fired == 0
    # a brief spike: fast burn is high but the slow window still
    # averages it away — no fire (the flapping gate)
    for _ in range(4):
        h.record(5.0)
    t += 5.0
    a.evaluate(t)
    st = a.state()["rules"]["e2e<=1s@0.9"]
    assert st["state"] == "ok" and st["burn_fast"] >= 3.0
    # sustained regression: both windows burn — exactly one fire
    for _ in range(6):
        for _ in range(4):
            h.record(5.0)
        t += 5.0
        a.evaluate(t)
    assert a.fired == 1 and a.active() == 1
    # recovery: healthy samples drain the fast window — one clear
    for _ in range(4):
        for _ in range(8):
            h.record(0.1)
        t += 5.0
        a.evaluate(t)
    assert a.cleared == 1 and a.active() == 0
    assert a.fired == 1                  # never re-fired
    return a.decisions


def test_burn_rate_fsm_fires_once_and_is_deterministic():
    d1 = _scripted_burn_run()
    d2 = _scripted_burn_run()
    assert [e["action"] for e in d1] == ["fire", "clear"]
    assert d1 == d2


def test_bad_fraction_counts_threshold_bucket_as_good():
    h = HistogramCounter()
    base = h.snapshot()
    h.record(0.9)                        # same bucket as threshold 1.0
    h.record(8.0)                        # clearly bad
    frac, n = SloAlerts._bad_fraction(h, h.snapshot(), base, 1.0)
    assert n == 2 and frac == 0.5


def test_server_alert_fires_once_under_seeded_regression(
        params, knobs, tmp_path):
    """The live path: a seeded decode-fault burst inflates decode
    stalls (retry backoff) past the rule threshold — the flush-boundary
    evaluator fires EXACTLY once, captures a slo_alert flight bundle,
    and clears after recovery."""
    knobs("hpx.obs.alerts", "1")
    knobs("hpx.obs.alert_rules", "decode_stall:0.08:0.9")
    knobs("hpx.obs.alert_fast_s", "0.5")
    knobs("hpx.obs.alert_slow_s", "1.5")
    knobs("hpx.obs.alert_burn_fast", "3")
    knobs("hpx.obs.alert_burn_slow", "1.5")
    knobs("hpx.obs.alert_interval_s", "0.02")
    knobs("hpx.flight.dir", str(tmp_path))
    knobs("hpx.serving.retry_backoff_s", "0.2")
    srv = ContinuousServer(params, CFG, slots=2, smax=64)
    assert srv._alerts is not None
    for p, m in [([3, 1, 4], 24), ([2, 7], 24), ([5, 6], 24)]:
        srv.submit(p, max_new=m)
    faultinject.install(faultinject.FaultInjector(
        seed=0, schedule={"decode": set(range(2, 16, 2))}))
    try:
        srv.run()
    finally:
        faultinject.uninstall()
    assert srv._alerts.fired == 1
    bundles = [n for n in tmp_path.iterdir()
               if n.name.endswith("-slo_alert.json")]
    assert len(bundles) == 1
    doc = json.loads(bundles[0].read_text())
    assert doc["trigger"]["kind"] == "slo_alert"
    assert doc["extra"]["rule"].startswith("decode_stall")
    # recovery: once the fast window drains past the fault burst,
    # healthy samples clear the alert — and it never re-fires
    import time
    time.sleep(0.6)
    for _ in range(5):
        srv.hist["decode_stall"].record(0.001)
    srv._alerts.evaluate()
    assert srv._alerts.active() == 0
    assert srv._alerts.cleared == 1 and srv._alerts.fired == 1


def test_alerts_off_is_none(params):
    srv = ContinuousServer(params, CFG, slots=2, smax=64)
    assert srv._alerts is None           # zero-overhead gate
    assert srv.hist["e2e"]._ex is None


# ---------------------------------------------------------------------------
# flight --list CLI
# ---------------------------------------------------------------------------

def test_flight_list_cli(knobs, tmp_path, capsys):
    knobs("hpx.flight.dir", str(tmp_path))
    flight.record_fault("slo_alert", site="slo/e2e<=1s@0.9")
    import time
    time.sleep(0.02)                     # distinct mtimes for the sort
    flight.record_fault("manual", site="cli")
    assert flight.main(["--list"]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    assert len(lines) == 2
    # newest first; reason/site/schema on every line
    assert "reason=manual" in lines[0]
    assert "reason=slo_alert" in lines[1]
    assert "slo_alert" in lines[1].split()[0]   # kind survives sanitize
    assert all("schema=hpx_tpu.flight.v1" in ln for ln in lines)
    assert flight.main(["--list", "--tail", "1"]) == 0
    assert len(capsys.readouterr().out.strip().splitlines()) == 1
    # bundle_index carries the same rows /flightz serves
    idx = flight.bundle_index()
    assert [e["reason"] for e in idx] == ["manual", "slo_alert"]
    # no args: usage + exit 2, the dump subcommand still works
    assert flight.main([]) == 2


# ---------------------------------------------------------------------------
# opsplane smoke: ephemeral port during a real serving run
# ---------------------------------------------------------------------------

def _get(url, accept=None):
    req = urllib.request.Request(url)
    if accept:
        req.add_header("Accept", accept)
    with urllib.request.urlopen(req, timeout=10) as r:
        return r.status, r.headers.get("Content-Type"), r.read().decode()


_PROM_LINE = re.compile(
    r'^[A-Za-z_:][A-Za-z0-9_:]*(\{[^}]*\})? \S+'
    r'( # \{[^}]*\} \S+ \S+)?$')


def test_opsplane_smoke_during_serving_run(params, knobs):
    """The CI tier-1 smoke: boot the plane on an ephemeral port, run a
    real ContinuousServer with exemplars+alerts on, and scrape every
    route while the process is live."""
    knobs("hpx.obs.port", "0")
    knobs("hpx.obs.exemplars", "1")
    knobs("hpx.obs.exemplar_quantile", "0.5")
    knobs("hpx.obs.alerts", "1")
    try:
        srv = ContinuousServer(params, CFG, slots=2, smax=64)
        plane = opsplane.active_opsplane()
        assert plane is not None and plane.port > 0
        a = srv.submit([3, 1, 4], max_new=6)
        b = srv.submit([2, 7], max_new=4)
        out = srv.run()
        assert set(out) == {a, b}

        # /varz default: every line parses as v0.0.4 text, no # EOF
        code, ctype, body = _get(f"{plane.url}/varz")
        assert code == 200 and ctype == metrics.PROM_CONTENT_TYPE
        lines = body.strip().splitlines()
        assert lines and "# EOF" not in body
        for ln in lines:
            assert ln.startswith("# ") or _PROM_LINE.match(ln), ln

        # /varz negotiated: OpenMetrics with terminator; exemplar rids
        # resolve to live request timelines
        code, ctype, body = _get(f"{plane.url}/varz",
                                 accept=metrics.OPENMETRICS_CONTENT_TYPE)
        assert code == 200 and ctype == metrics.OPENMETRICS_CONTENT_TYPE
        assert body.rstrip().endswith("# EOF")
        ex_rids = [int(m) for m in re.findall(r'# \{rid="(\d+)"\}', body)]
        assert ex_rids
        for rid in set(ex_rids):
            names = {e["name"] for e in srv.timeline.events(rid)}
            assert "submit" in names and "retire" in names

        # /statusz: valid JSON with the tier flight snapshot and this
        # server's provider section
        code, _, body = _get(f"{plane.url}/statusz")
        doc = json.loads(body)
        assert code == 200 and "tier" in doc
        sect = doc["providers"][f"serving/{srv.counter_instance}"]
        assert sect["kind"] == "server" and sect["slots"] == 2
        assert sect["timeline_rids"] == 2 and sect["live_slots"] == 0
        assert "alerts" in sect

        # /healthz: ok (nothing fired), /tracez + /flightz respond,
        # unknown routes 404
        code, _, body = _get(f"{plane.url}/healthz")
        assert code == 200 and json.loads(body)["status"] == "ok"
        code, _, body = _get(f"{plane.url}/tracez")
        assert code == 200 and "spans" in json.loads(body)
        code, _, body = _get(f"{plane.url}/flightz")
        assert code == 200 and "bundles" in json.loads(body)
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(f"{plane.url}/nope")
        assert ei.value.code == 404

        # provider prunes after the server dies
        del srv, sect
        import gc
        gc.collect()
        code, _, body = _get(f"{plane.url}/statusz")
        assert not any(k.startswith("serving/")
                       for k in json.loads(body)["providers"])
    finally:
        opsplane.stop_opsplane()


def test_opsplane_off_by_default(params):
    assert opsplane.ensure_opsplane() is None
    assert opsplane.active_opsplane() is None

"""Prometheus exposition correctness + metric-naming lint.

A minimal text-format parser validates the FULL registry exposition:
HELP/TYPE pairing, label escaping, histogram bucket monotonicity — so
a malformed family breaks a fast test here instead of a scraper in
production.  The naming lint (counters end ``_total``, durations end
``_seconds``, no colliding families) runs against GLOBAL_REGISTRY after
importing the node modules, so every metric the node actually registers
is covered.
"""

import re

import pytest

from teku_tpu.infra.metrics import (Counter, Gauge, Histogram,
                                    LabeledCounter, LabeledGauge,
                                    LabeledHistogram, LATENCY_BUCKETS_S,
                                    MetricsRegistry, StateGauge)

_SAMPLE_RE = re.compile(
    r'^(?P<name>[a-zA-Z_:][a-zA-Z0-9_:]*)'
    r'(?:\{(?P<labels>.*)\})?'
    r' (?P<value>[^ ]+)$')
_LABEL_RE = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"')


def _unescape(value: str) -> str:
    return (value.replace("\\n", "\n").replace('\\"', '"')
            .replace("\\\\", "\\"))


def parse_exposition(text: str):
    """Parse Prometheus text format into
    {family: {"type", "help", "samples": [(name, labels, value)]}}.
    Raises AssertionError on any structural violation."""
    families: dict = {}
    current = None
    for lineno, line in enumerate(text.splitlines(), 1):
        if not line.strip():
            continue
        if line.startswith("# HELP "):
            _, _, rest = line.partition("# HELP ")
            name, _, help_ = rest.partition(" ")
            assert name not in families, \
                f"line {lineno}: duplicate HELP for {name}"
            families[name] = {"help": help_, "type": None, "samples": []}
            current = name
            continue
        if line.startswith("# TYPE "):
            _, _, rest = line.partition("# TYPE ")
            name, _, type_ = rest.partition(" ")
            assert name == current, \
                f"line {lineno}: TYPE {name} not paired under HELP"
            assert type_ in ("counter", "gauge", "histogram", "summary")
            assert families[name]["type"] is None, \
                f"line {lineno}: duplicate TYPE for {name}"
            families[name]["type"] = type_
            continue
        assert not line.startswith("#"), f"line {lineno}: bad comment"
        m = _SAMPLE_RE.match(line)
        assert m, f"line {lineno}: unparsable sample {line!r}"
        name = m.group("name")
        family = name
        for suffix in ("_bucket", "_sum", "_count"):
            if name.endswith(suffix) and name[:-len(suffix)] in families:
                family = name[:-len(suffix)]
        assert family in families, \
            f"line {lineno}: sample {name} outside any HELP/TYPE family"
        raw = m.group("labels") or ""
        labels = {k: _unescape(v) for k, v in _LABEL_RE.findall(raw)}
        if raw:
            # every label pair must parse (catches broken escaping)
            rebuilt = ",".join(f'{k}="{v}"'
                               for k, v in _LABEL_RE.findall(raw))
            assert rebuilt == raw, \
                f"line {lineno}: malformed labels {raw!r}"
        value = float(m.group("value")) if m.group("value") != "+Inf" \
            else float("inf")
        families[family]["samples"].append((name, labels, value))
        current = family
    for name, fam in families.items():
        assert fam["type"] is not None, f"family {name} missing TYPE"
    return families


def _histogram_checks(fam, family_name):
    """le-monotonicity + bucket/sum/count coherence per label set."""
    by_labelset: dict = {}
    for name, labels, value in fam["samples"]:
        key = tuple(sorted((k, v) for k, v in labels.items()
                           if k != "le"))
        entry = by_labelset.setdefault(
            key, {"buckets": [], "sum": None, "count": None})
        if name.endswith("_bucket"):
            le = labels["le"]
            entry["buckets"].append(
                (float("inf") if le == "+Inf" else float(le), value))
        elif name.endswith("_sum"):
            entry["sum"] = value
        elif name.endswith("_count"):
            entry["count"] = value
    assert by_labelset, f"{family_name}: no samples"
    for key, entry in by_labelset.items():
        buckets = entry["buckets"]
        assert buckets, f"{family_name}{key}: no buckets"
        les = [le for le, _ in buckets]
        counts = [c for _, c in buckets]
        assert les == sorted(les), f"{family_name}{key}: le unsorted"
        assert les[-1] == float("inf"), \
            f"{family_name}{key}: missing +Inf bucket"
        assert counts == sorted(counts), \
            f"{family_name}{key}: cumulative counts not monotone"
        assert entry["count"] == counts[-1], \
            f"{family_name}{key}: count != +Inf bucket"
        assert entry["sum"] is not None


def test_full_exposition_parses_and_validates():
    reg = MetricsRegistry()
    reg.counter("requests_total", "requests").inc(3)
    reg.gauge("depth", "queue depth").set(7)
    h = reg.histogram("sizes", "batch sizes", buckets=(1, 10, 100))
    h.observe(5)
    h.observe(5000)
    lc = reg.labeled_counter(
        "outcomes_total", "labeled outcomes",
        labelnames=("backend", "reason"))
    lc.labels(backend="device", reason="ok").inc()
    lc.labels(backend="oracle", reason='we "quoted" a\\slash\nnewline'
              ).inc(2)
    lh = reg.labeled_histogram(
        "stage_seconds", "stage durations", labelnames=("stage",))
    lh.labels(stage="device_execute").observe(0.004)
    lh.labels(stage="queue_wait").observe(11.0)   # overflows to +Inf
    sg = reg.state_gauge("backend_state", "state set",
                         states=("cold", "ready"))
    sg.set_state("ready")

    fams = parse_exposition(reg.expose())
    assert fams["requests_total"]["type"] == "counter"
    assert fams["requests_total"]["samples"][0][2] == 3.0
    assert fams["depth"]["type"] == "gauge"
    assert fams["sizes"]["type"] == "histogram"
    _histogram_checks(fams["sizes"], "sizes")
    _histogram_checks(fams["stage_seconds"], "stage_seconds")
    # label escaping round-trips through the parser
    oracle = [s for s in fams["outcomes_total"]["samples"]
              if s[1].get("backend") == "oracle"]
    assert oracle[0][1]["reason"] == 'we "quoted" a\\slash\nnewline'
    assert oracle[0][2] == 2.0
    # state set: exactly one series at 1.0
    states = fams["backend_state"]["samples"]
    assert sum(v for _, _, v in states) == 1.0
    assert [s for _, s, v in states if v == 1.0][0]["state"] == "ready"


def test_raising_gauge_supplier_does_not_break_scrape():
    reg = MetricsRegistry()
    reg.counter("alive_total", "proof of scrape").inc()

    def boom():
        raise RuntimeError("supplier died")

    reg.gauge("sick", "raising supplier", supplier=boom)
    text = reg.expose()
    fams = parse_exposition(text)
    # the scrape survives; the healthy metric is present with a value,
    # the sick gauge lost only its sample
    assert fams["alive_total"]["samples"][0][2] == 1.0
    assert fams["sick"]["samples"] == []


def test_help_lines_emitted_for_every_family():
    reg = MetricsRegistry()
    reg.counter("a_total", "help a")
    reg.histogram("b_seconds", "help b", buckets=LATENCY_BUCKETS_S)
    text = reg.expose()
    assert "# HELP a_total help a" in text
    assert "# HELP b_seconds help b" in text
    # HELP precedes TYPE for each family
    lines = text.splitlines()
    for name in ("a_total", "b_seconds"):
        help_i = lines.index(f"# HELP {name} help {name[0]}")
        type_i = next(i for i, l in enumerate(lines)
                      if l.startswith(f"# TYPE {name} "))
        assert help_i + 1 == type_i


def test_labeled_counter_label_validation():
    reg = MetricsRegistry()
    lc = reg.labeled_counter("x_total", "x", labelnames=("a", "b"))
    with pytest.raises(ValueError):
        lc.labels(a="1")              # missing label
    with pytest.raises(ValueError):
        lc.labels(a="1", b="2", c="3")  # extra label
    with pytest.raises(ValueError):
        reg.labeled_counter("x_total", "x", labelnames=("other",))
    with pytest.raises(ValueError):
        reg.counter("x_total")        # type mismatch on re-registration


# --------------------------------------------------------------------------
# Naming lint: run against the GLOBAL registry after importing the node
# modules, so every metric the node wires is checked
# --------------------------------------------------------------------------

_DURATION_HINT = re.compile(r"(duration|latency|_wait|elapsed)")
_UNIT_SUFFIXES = ("_seconds", "_ratio", "_bytes")


def test_metric_naming_lint_after_node_imports():
    import teku_tpu.crypto.bls.loader  # noqa: F401
    import teku_tpu.infra.supervisor  # noqa: F401
    import teku_tpu.infra.tracing  # noqa: F401
    import teku_tpu.node.node  # noqa: F401
    import teku_tpu.ops.provider  # noqa: F401
    import teku_tpu.services.signatures  # noqa: F401
    from teku_tpu.infra.metrics import GLOBAL_REGISTRY

    metrics = GLOBAL_REGISTRY.metrics()
    assert metrics, "node imports registered no metrics"
    problems = []
    names = set(metrics)
    for name, m in metrics.items():
        if isinstance(m, (Counter, LabeledCounter)):
            if not name.endswith("_total"):
                problems.append(f"counter {name} must end _total")
        if isinstance(m, (Histogram, LabeledHistogram, Gauge)):
            if _DURATION_HINT.search(name) \
                    and not name.endswith("_seconds"):
                problems.append(
                    f"duration metric {name} must end _seconds")
        if isinstance(m, (Histogram, LabeledHistogram)) \
                and name.endswith("_seconds"):
            if max(m.buckets) > 100:
                problems.append(
                    f"histogram {name} is *_seconds but its buckets "
                    f"({m.buckets[:3]}…{m.buckets[-1]}) look like "
                    "unitless DEFAULT_BUCKETS — use LATENCY_BUCKETS_S")
        if isinstance(m, (Histogram, LabeledHistogram)):
            # derived series must not collide with another family
            for suffix in ("_bucket", "_sum", "_count"):
                if name + suffix in names:
                    problems.append(
                        f"{name + suffix} collides with histogram "
                        f"{name}'s derived series")
    assert not problems, "\n".join(problems)


def test_global_exposition_is_well_formed_after_node_imports():
    import teku_tpu.node.node  # noqa: F401
    from teku_tpu.infra.metrics import GLOBAL_REGISTRY

    fams = parse_exposition(GLOBAL_REGISTRY.expose())
    assert "verify_stage_duration_seconds" in fams
    assert "bls_dispatch_padding_waste_ratio" in fams


_JIT_OUTCOMES = {"compile", "cache_load", "aot_load", "cache_hit"}


def test_dispatch_and_cache_label_contract():
    """The mont-path/compile-cache label vocabulary must not drift:
    dashboards key on `path` (vpu|mxu) and the four-way jit outcome
    (compile = fresh XLA work, cache_load = served from the persistent
    cache dir, aot_load = deserialized from the AOT executable store,
    cache_hit = in-memory jit cache)."""
    from teku_tpu.infra import compilecache  # noqa: F401 - registers
    from teku_tpu.infra.metrics import GLOBAL_REGISTRY
    import teku_tpu.ops.provider as pv
    from teku_tpu.ops import mxu

    metrics = GLOBAL_REGISTRY.metrics()
    jit = metrics["bls_jit_dispatch_total"]
    assert isinstance(jit, LabeledCounter)
    assert tuple(jit.labelnames) == ("shape", "outcome", "path")
    cache = metrics["xla_compile_cache_total"]
    assert isinstance(cache, LabeledCounter)
    assert tuple(cache.labelnames) == ("outcome",)
    # the classifier can only emit the documented vocabulary
    for d in ({"hits": 1, "misses": 0}, {"hits": 0, "misses": 1},
              {"hits": 3, "misses": 2}, {"hits": 0, "misses": 0}):
        assert compilecache.classify_first_dispatch(d) in _JIT_OUTCOMES
    # the AOT executable store adds the fourth outcome: a first
    # dispatch served by deserialization (no compile, no cache load)
    assert compilecache.classify_first_dispatch(
        {"hits": 0, "misses": 0},
        aot={"loads": 1, "misses": 0, "saves": 0, "errors": 0}) \
        == "aot_load"
    assert "aot_load" in _JIT_OUTCOMES
    # and the path label values come from the resolver's closed set
    assert mxu.resolve() in ("vpu", "mxu")
    # provider records its engine for introspection
    assert pv  # imported above; JaxBls12381 instances carry .mont_path


def test_dispatch_prep_family_label_contract():
    """The per-dispatch families a verify dispatch moves besides the
    jit-outcome counter must not drift: `bls_dispatch_prep_total`
    carries exactly (`prep`, `reason`) from CLOSED sets — where the
    host prep ran, and why it stayed under the device-entry lock — and
    the h2c dedup counters are unlabeled (one scalars stage: nothing
    about a dispatch is split by a kernel choice)."""
    import teku_tpu.ops.provider  # noqa: F401 - registers families
    from teku_tpu.infra.metrics import GLOBAL_REGISTRY

    metrics = GLOBAL_REGISTRY.metrics()
    prep = metrics["bls_dispatch_prep_total"]
    assert isinstance(prep, LabeledCounter)
    assert tuple(prep.labelnames) == ("prep", "reason")
    vocab = {("outside_lock", "none"), ("under_lock", "pk_miss"),
             ("under_lock", "arena"), ("under_lock", "pk_miss+arena")}
    # any series already recorded stays inside the closed set
    for key, _child in prep._items():
        assert key in vocab, key
    for fam in ("bls_h2c_lanes_total", "bls_h2c_unique_total",
                "bls_h2c_dispatch_total"):
        assert isinstance(metrics[fam], Counter), fam


def test_prep_turn_family_label_contract():
    """`bls_prep_turn_total` carries exactly one `released` label from
    the CLOSED set of ways a guarded dispatch with a host half gives the
    packers' turn back (after its launches, on a host verdict, on a
    raise), one series each; a provider without a host half moves
    none."""
    from teku_tpu.crypto.bls import loader
    from teku_tpu.crypto.bls.spi import PreparedDispatch, ResolvedHandle
    from teku_tpu.infra.metrics import GLOBAL_REGISTRY
    from teku_tpu.infra.supervisor import CircuitBreaker

    vocab = ("launched", "host_verdict", "error")

    class Halves:
        name = "halves"

        def prepare_dispatch(self, op, triples):
            message = triples[0][1]
            if message == b"raise":
                raise RuntimeError("host half failed")
            return PreparedDispatch(False if message == b"host" else None)

        def launch_dispatch(self, prepared):
            return ResolvedHandle(True)

        def batch_verify(self, triples):    # the halves go around it
            raise AssertionError("the guard bypassed the two halves")

    class Whole:
        name = "whole"

        def batch_verify(self, triples):
            return True

    class Oracle:
        def batch_verify(self, triples):
            return True

    reg = MetricsRegistry()
    for device, messages in ((Halves(), (b"launch", b"host", b"raise")),
                             (Whole(), (b"whole",))):
        breaker = CircuitBreaker(failure_threshold=3, deadline_s=10.0,
                                 cooldown_s=60.0, name="lint_turn",
                                 registry=reg)
        guarded = loader.GuardedBls12381(device, breaker, oracle=Oracle(),
                                         registry=reg)
        for message in messages:
            guarded.batch_verify([([b"pk"], message, b"sig")])
    fam = reg.metrics()["bls_prep_turn_total"]
    assert isinstance(fam, LabeledCounter)
    assert tuple(fam.labelnames) == ("released",)
    assert {key: child.value for key, child in fam._items()} == {
        (released,): 1 for released in vocab}
    assert "bls_prep_turn_total" in parse_exposition(reg.expose())
    # any series the global registry already holds stays inside the set
    glob = GLOBAL_REGISTRY.metrics().get("bls_prep_turn_total")
    for key, _child in (glob._items() if glob is not None else ()):
        assert key[0] in vocab, key


def test_key_slot_family_label_contract():
    """The key axis's two counters carry exactly one `kmax` label from
    the CLOSED power-of-two vocabulary `shapeset.kmax_bucket` emits
    (keys a lane, padded; a committee or the sync committee is 512, an
    Electra block's slot-wide vote 32,768 at ~1M validators, the
    provider's ceiling `shapeset.MAX_KEYS_PER_LANE` 131,072), and a
    dispatch moves the filled one by no more than the dispatched one."""
    import teku_tpu.ops.provider  # noqa: F401 - registers families
    from teku_tpu.infra.metrics import GLOBAL_REGISTRY
    from teku_tpu.ops import shapeset

    metrics = GLOBAL_REGISTRY.metrics()
    pow2_vocab = {str(1 << i) for i in range(0, 18)}    # 1..131072
    assert {str(shapeset.kmax_bucket(k))
            for k in (1, 2, 3, 400, 488, 512, 2048, 31_296,
                      shapeset.MAX_KEYS_PER_LANE)} <= pow2_vocab
    filled = metrics["bls_key_slots_filled_total"]
    slots = metrics["bls_key_slots_dispatched_total"]
    for fam in (filled, slots):
        assert isinstance(fam, LabeledCounter)
        assert tuple(fam.labelnames) == ("kmax",)
        for key, _child in fam._items():
            assert set(key) <= pow2_vocab, key
    dispatched = {key: child.value for key, child in slots._items()}
    for key, child in filled._items():
        assert 0 < child.value <= dispatched[key]


def test_warmup_dispatch_family_label_contract():
    """`bls_warmup_dispatches_total` carries exactly (`profile`,
    `kmax`): the names of `shapeset.warmup_profiles` (x1,
    x<max_batch>, x<max_batch>dup8, aggregate, aggregate_forged, block,
    block_forged, block_narrow) and the power-of-two key buckets
    `shapeset.kmax_bucket` emits, up to the block bucket; one warm
    dispatch moves its own series by one."""
    import re

    from teku_tpu.crypto.bls import loader
    from teku_tpu.infra.metrics import GLOBAL_REGISTRY
    from teku_tpu.ops import shapeset

    names = re.compile(r"^(x1|x\d+|x\d+dup8|aggregate|aggregate_forged"
                       r"|block|block_forged|block_narrow)$")
    assert all(names.match(name) for name, *_ in
               shapeset.warmup_profiles(256, 512, 32_768))

    class Device:
        min_bucket = 16

        def batch_verify(self, triples):
            return True

    fam = GLOBAL_REGISTRY.metrics()["bls_warmup_dispatches_total"]
    assert isinstance(fam, LabeledCounter)
    assert tuple(fam.labelnames) == ("profile", "kmax")
    before = fam.labels(profile="x1", kmax="1").value
    loader._warm_dispatch(Device(), "x1", 1, [([b"pk"], b"m", b"s")],
                          True)
    assert fam.labels(profile="x1", kmax="1").value == before + 1
    pow2_vocab = {str(1 << i) for i in range(0, 18)}
    for (profile, kmax), _child in fam._items():
        assert names.match(profile) and kmax in pow2_vocab, (profile,
                                                              kmax)
    assert "bls_warmup_dispatches_total" in parse_exposition(
        GLOBAL_REGISTRY.expose())


def test_service_task_and_triple_families():
    """The batching service counts tasks, their triples and the tasks
    of several triples apart, name-prefixed like its other families
    and present from scrape 1 (triples / tasks is the lanes a task
    takes; a dashboard divides two series that both exist)."""
    from teku_tpu.services.signatures import (
        AggregatingSignatureVerificationService)
    reg = MetricsRegistry()
    AggregatingSignatureVerificationService(registry=reg, name="lint_svc")
    metrics = reg.metrics()
    for fam in ("lint_svc_task_count_total",
                "lint_svc_triple_count_total",
                "lint_svc_multi_task_count_total"):
        assert isinstance(metrics[fam], Counter), fam
        assert metrics[fam].value == 0
    fams = parse_exposition(reg.expose())
    assert {"lint_svc_triple_count_total",
            "lint_svc_multi_task_count_total"} <= set(fams)


def test_mesh_family_label_contract():
    """The PR-10 mesh families must not drift: the sharded-dispatch
    counter carries exactly one `devices` label whose values come from
    the CLOSED pow-2 vocabulary resolve_mesh_devices can emit, the
    process gauge is `bls_mesh_devices`, and supervisors export a
    name-prefixed mesh gauge (multi-node devnets keep series
    distinct, like the admission families)."""
    import teku_tpu.ops.provider  # noqa: F401 - registers families
    from teku_tpu import parallel
    from teku_tpu.crypto.bls import loader
    from teku_tpu.infra.metrics import GLOBAL_REGISTRY

    metrics = GLOBAL_REGISTRY.metrics()
    fam = metrics["bls_mesh_dispatch_total"]
    assert isinstance(fam, LabeledCounter)
    assert tuple(fam.labelnames) == ("devices",)
    # closed vocabulary: pow-2 device counts (the resolver only ever
    # yields pow-2 mesh sizes; bounded — label cardinality is the
    # handful of mesh sizes a fleet actually runs)
    pow2_vocab = {str(1 << i) for i in range(1, 9)}   # 2..256
    for key, _child in fam._items():
        assert set(key) <= pow2_vocab, key
    # the resolver can only emit 0 (off) or a pow-2 >= 2
    for spec, avail in (("auto", 8), ("auto", 5), ("auto", 1),
                        ("6", 8), ("100", 8), ("3", 4), ("off", 8),
                        ("garbage", 8)):
        n = parallel.resolve_mesh_devices(spec, available=avail)
        assert n == 0 or (n >= 2 and n & (n - 1) == 0), (spec, n)
    assert isinstance(metrics["bls_mesh_devices"], Gauge)
    # the supervisor-scoped gauge is name-prefixed
    reg = MetricsRegistry()
    loader.make_supervisor(registry=reg, warm=False,
                           name="lint_mesh",
                           breaker_name="lint_mesh_dev")
    assert isinstance(reg.metrics()["lint_mesh_mesh_devices"], Gauge)


def test_mesh_selfheal_family_label_contract():
    """The self-healing families must not drift: the reshape counter
    carries exactly ``{direction, devices}`` with direction from the
    closed {shrink, grow} set and devices from {0, 1} ∪ pow-2 (the
    healer's largest-surviving-pow-2 rule plus the single-device and
    oracle floors), and the recovery/ejection readouts are plain
    gauges."""
    from teku_tpu.infra.metrics import GLOBAL_REGISTRY
    from teku_tpu.parallel import selfheal

    metrics = GLOBAL_REGISTRY.metrics()
    fam = metrics["bls_mesh_reshape_total"]
    assert isinstance(fam, LabeledCounter)
    assert tuple(fam.labelnames) == ("direction", "devices")
    assert selfheal.DIRECTIONS == ("shrink", "grow")
    devices_vocab = {"0", "1"} | {str(1 << i) for i in range(1, 9)}
    for (direction, devices), _child in fam._items():
        assert direction in selfheal.DIRECTIONS, direction
        assert devices in devices_vocab, devices
    assert isinstance(metrics["bls_mesh_recovery_seconds"], Gauge)
    assert isinstance(metrics["bls_mesh_ejected_devices"], Gauge)
    # the flight-event kinds the doctor joins on are spelled once
    # (a typo'd kind string would silently disable the findings)
    from teku_tpu.infra import doctor
    import inspect
    src = inspect.getsource(doctor._mesh_health_findings)
    for kind in ("mesh_eject", "mesh_reshape", "mesh_readmit"):
        assert kind in src


def test_h2c_dedup_and_coalesce_family_naming_lint():
    """The PR-5 dedup/cache/coalesce families must not drift: hit/miss/
    evict/dispatch counters end ``_total``, the dedup gauge is a
    unitless ``_ratio``, the shared eviction family is labeled by
    cache, and the service coalesce counter follows the service's
    ``<name>_*_total`` convention."""
    import teku_tpu.ops.h2c_cache  # noqa: F401 - registers families
    import teku_tpu.ops.provider  # noqa: F401
    from teku_tpu.infra.metrics import GLOBAL_REGISTRY
    from teku_tpu.services.signatures import (
        AggregatingSignatureVerificationService)

    # instantiating registers the per-service families (idempotent)
    reg = MetricsRegistry()
    AggregatingSignatureVerificationService(registry=reg)
    assert isinstance(
        reg.metrics()["signature_verifications_coalesced_total"],
        Counter)

    metrics = GLOBAL_REGISTRY.metrics()
    assert {"bls_h2c_cache_hits_total", "bls_h2c_cache_misses_total",
            "bls_cache_evictions_total", "bls_h2c_dispatch_total",
            "bls_h2c_lanes_total", "bls_h2c_unique_total",
            "bls_h2c_dedup_ratio"} <= set(metrics)
    evict = metrics["bls_cache_evictions_total"]
    assert isinstance(evict, LabeledCounter)
    assert tuple(evict.labelnames) == ("cache",)
    assert isinstance(metrics["bls_h2c_dedup_ratio"], Gauge)
    problems = []
    for name, m in metrics.items():
        if not name.startswith(("bls_h2c_", "bls_cache_")):
            continue
        if isinstance(m, (Counter, LabeledCounter)) \
                and not name.endswith("_total"):
            problems.append(f"counter {name} must end _total")
        if name.endswith("_total") \
                and not isinstance(m, (Counter, LabeledCounter)):
            problems.append(f"{name} ends _total but is not a counter")
        if isinstance(m, Gauge) and not name.endswith(_UNIT_SUFFIXES):
            problems.append(
                f"gauge {name} needs a unit suffix (_ratio for the "
                "dedup/waste observables)")
    assert not problems, "\n".join(problems)
    # dedup ratio stays in [0, 1): lanes >= uniques by construction
    from teku_tpu.ops.provider import _dedup_ratio
    assert 0.0 <= _dedup_ratio() < 1.0


def test_capacity_profiler_family_naming_lint():
    """The capacity/occupancy + profiler families must not drift:
    HELP/TYPE pairing on the exposition, counters ``_total``, durations
    ``_seconds``, ratios ``_ratio`` / rates ``_per_second``, and a
    BOUNDED ``shape`` label cardinality on the device-latency model
    (pow-2 bucketing keeps the real set tiny; an adversarial shape
    storm must fold into "other", never grow the scrape)."""
    from teku_tpu.infra import capacity, profiling  # noqa: F401
    from teku_tpu.infra.capacity import ShapeLatencyModel
    from teku_tpu.infra.metrics import GLOBAL_REGISTRY

    metrics = GLOBAL_REGISTRY.metrics()
    assert {"bls_shape_device_latency_seconds",
            "bls_arrival_rate_per_second", "bls_queue_depth",
            "bls_device_occupancy_ratio",
            "capacity_shed_rate_per_second",
            "capacity_sustainable_sigs_per_second",
            "capacity_utilization_ratio", "capacity_headroom_ratio",
            "profiler_captures_total"} <= set(metrics)
    lat = metrics["bls_shape_device_latency_seconds"]
    assert isinstance(lat, LabeledGauge)
    assert tuple(lat.labelnames) == ("shape", "path", "stat")
    arrival = metrics["bls_arrival_rate_per_second"]
    assert isinstance(arrival, LabeledGauge)
    assert tuple(arrival.labelnames) == ("source",)
    captures = metrics["profiler_captures_total"]
    assert isinstance(captures, LabeledCounter)
    assert tuple(captures.labelnames) == ("trigger",)

    problems = []
    for name, m in metrics.items():
        if not name.startswith(("capacity_", "profiler_",
                                "bls_shape_", "bls_arrival_",
                                "bls_device_occupancy")):
            continue
        if isinstance(m, (Counter, LabeledCounter)) \
                and not name.endswith("_total"):
            problems.append(f"counter {name} must end _total")
        if name.endswith("_total") \
                and not isinstance(m, (Counter, LabeledCounter)):
            problems.append(f"{name} ends _total but is not a counter")
        if _DURATION_HINT.search(name) and not name.endswith("_seconds"):
            problems.append(f"duration metric {name} must end _seconds")
        if isinstance(m, (Gauge, LabeledGauge)) \
                and not name.endswith(
                    ("_seconds", "_ratio", "_per_second", "_depth")):
            problems.append(
                f"gauge {name} needs a unit suffix (_seconds, _ratio, "
                "_per_second)")
    assert not problems, "\n".join(problems)

    # bounded `shape` cardinality: 40 distinct shapes collapse to the
    # model's cap + the "other" overflow series, on the exported gauge
    reg = MetricsRegistry()
    model = ShapeLatencyModel(max_shapes=8, registry=reg)
    for i in range(40):
        model.observe(f"{i}x{i}", "vpu", 0.001)
    gauge = reg.metrics()["bls_shape_device_latency_seconds"]
    shapes = {key[0] for key, _ in gauge._items()}
    assert len(shapes) == 9 and ShapeLatencyModel.OVERFLOW in shapes

    # the exposition stays structurally valid (HELP/TYPE pairing) with
    # every new family present
    fams = parse_exposition(GLOBAL_REGISTRY.expose())
    for fam in ("bls_shape_device_latency_seconds",
                "capacity_utilization_ratio",
                "profiler_captures_total"):
        assert fam in fams and fams[fam]["type"] is not None


def test_overload_class_family_naming_lint():
    """The PR-7 per-class/admission families must not drift: every
    ``{class}`` label value comes from the CLOSED VerifyClass enum
    (bounded cardinality — an adversary cannot grow the scrape by
    inventing classes, because the label is typed at the API), sheds
    are ``_total`` counters labeled by class, the per-class depth/age
    gauges carry unit suffixes, and the admission controller exports
    its plan/brownout gauges + edge-transition counter."""
    from teku_tpu.services.admission import (AdmissionController,
                                             CLASS_LABELS, VerifyClass)
    from teku_tpu.services.signatures import (
        AggregatingSignatureVerificationService)

    # the class label vocabulary IS the enum — closed and tiny
    assert CLASS_LABELS == ("vip", "block_import", "sync_critical",
                            "gossip", "optimistic")
    assert len(CLASS_LABELS) == len(VerifyClass)

    reg = MetricsRegistry()
    AggregatingSignatureVerificationService(registry=reg,
                                            name="lint_sigs")
    metrics = reg.metrics()
    rejected = metrics["lint_sigs_rejected_total"]
    assert isinstance(rejected, LabeledCounter)
    assert tuple(rejected.labelnames) == ("class",)
    depth = metrics["lint_sigs_class_queue_depth"]
    age = metrics["lint_sigs_class_oldest_wait_seconds"]
    assert isinstance(depth, LabeledGauge)
    assert isinstance(age, LabeledGauge)
    # bounded cardinality: the service pre-registers EXACTLY the enum's
    # series (scrape-complete from the first exposition, and nothing
    # can add a sixth class without extending the enum)
    assert {key[0] for key, _ in depth._items()} == set(CLASS_LABELS)
    assert {key[0] for key, _ in age._items()} == set(CLASS_LABELS)

    # admission controller families: name-prefixed like the service's
    # (a multi-node devnet process must not collapse every node onto
    # one shared gauge)
    reg2 = MetricsRegistry()
    from teku_tpu.infra.flightrecorder import FlightRecorder
    AdmissionController(registry=reg2, name="lint_adm",
                        recorder=FlightRecorder(registry=reg2))
    m2 = reg2.metrics()
    assert {"lint_adm_admission_batch_size",
            "lint_adm_admission_flush_deadline_seconds",
            "lint_adm_admission_brownout_level",
            "lint_adm_admission_brownout_transitions_total"} <= set(m2)
    trans = m2["lint_adm_admission_brownout_transitions_total"]
    assert isinstance(trans, LabeledCounter)
    assert tuple(trans.labelnames) == ("direction",)

    problems = []
    for name, m in {**metrics, **m2}.items():
        if not name.startswith(("lint_sigs_", "lint_adm_")):
            continue
        if isinstance(m, (Counter, LabeledCounter)) \
                and not name.endswith("_total"):
            problems.append(f"counter {name} must end _total")
        if name.endswith("_total") \
                and not isinstance(m, (Counter, LabeledCounter)):
            problems.append(f"{name} ends _total but is not a counter")
        if _DURATION_HINT.search(name) and not name.endswith("_seconds"):
            problems.append(f"duration metric {name} must end _seconds")
    assert not problems, "\n".join(problems)

    # the combined exposition stays structurally valid; the rejected
    # counter's family is DECLARED (HELP/TYPE) before any shed has
    # produced a series, so dashboards can discover it at scrape 1
    exposed = reg.expose()
    assert "# TYPE lint_sigs_rejected_total counter" in exposed
    fams = parse_exposition(exposed)
    for fam in ("lint_sigs_class_queue_depth",
                "lint_sigs_class_oldest_wait_seconds"):
        assert fam in fams and fams[fam]["type"] == "gauge"
        labels = {s[1].get("class") for s in fams[fam]["samples"]}
        assert labels == set(CLASS_LABELS)
    fams2 = parse_exposition(reg2.expose())
    assert fams2["lint_adm_admission_brownout_level"]["type"] == "gauge"


def test_queue_shed_events_carry_class_labels():
    """Flight-recorder queue_shed events must name the shed class and
    the shedding reason (the incident-report contract)."""
    import asyncio
    from teku_tpu.infra import flightrecorder
    from teku_tpu.services.admission import VerifyClass
    from teku_tpu.services.signatures import (
        AggregatingSignatureVerificationService,
        ServiceCapacityExceededError)

    async def main():
        svc = AggregatingSignatureVerificationService(
            num_workers=1, queue_capacity=1,
            registry=MetricsRegistry(), name="lint_shed")
        await svc.start()
        before = len(flightrecorder.RECORDER.snapshot())
        blocker = svc.verify([b"\xa0" + bytes(47)], b"b1", b"s")
        await asyncio.sleep(0.05)
        f1 = svc.verify([b"\xa0" + bytes(47)], b"b2", b"s",
                        cls=VerifyClass.OPTIMISTIC)
        with pytest.raises(ServiceCapacityExceededError):
            svc.verify([b"\xa0" + bytes(47)], b"b3", b"s",
                       cls=VerifyClass.OPTIMISTIC)
        for fut in (blocker, f1):
            try:
                await fut
            except Exception:
                pass
        await svc.stop()
        return flightrecorder.RECORDER.snapshot()[before:]

    events = asyncio.run(main())
    sheds = [e for e in events if e["kind"] == "queue_shed"]
    assert sheds, "no queue_shed event recorded"
    for e in sheds:
        assert e["class"] == "optimistic"
        assert e["reason"] in ("overflow", "preempted", "brownout")
        assert e["service"] == "lint_shed"
        assert "trace_id" in e


def test_slo_health_family_naming_lint():
    """The PR-3 families must not drift from the conventions: states as
    labeled/state gauges (never bare numbers encoding an enum), burn
    rates unitless gauges, durations ``_seconds``, counters
    ``_total``."""
    # importing + instantiating registers the families in the global
    # registry (idempotent: get_or_create)
    from teku_tpu.infra import flightrecorder  # noqa: F401
    from teku_tpu.infra.health import (EventLoopLagWatchdog,
                                       HealthRegistry, SloEngine)
    from teku_tpu.infra.metrics import GLOBAL_REGISTRY
    HealthRegistry(name="lint")
    SloEngine()

    metrics = {n: m for n, m in GLOBAL_REGISTRY.metrics().items()
               if n.startswith(("slo_", "health_"))}
    assert {"slo_burn_rate", "slo_breached", "slo_breaches_total",
            "health_node_state", "health_check_state",
            "health_transitions_total"} <= set(metrics)
    problems = []
    for name, m in metrics.items():
        if isinstance(m, (Counter, LabeledCounter)) \
                and not name.endswith("_total"):
            problems.append(f"counter {name} must end _total")
        if name.endswith("_total") \
                and not isinstance(m, (Counter, LabeledCounter)):
            problems.append(f"{name} ends _total but is not a counter")
        if _DURATION_HINT.search(name) and not name.endswith("_seconds"):
            problems.append(f"duration metric {name} must end _seconds")
        # states are gauges with a `state` dimension, not enum numbers
        if name.endswith("_state"):
            if isinstance(m, StateGauge):
                pass
            elif isinstance(m, LabeledGauge) \
                    and "state" in m.labelnames:
                pass
            else:
                problems.append(
                    f"{name} must be a StateGauge or a LabeledGauge "
                    "with a 'state' label")
        # burn rates are unitless ratios: no unit suffix allowed
        if "burn_rate" in name:
            if not isinstance(m, (Gauge, LabeledGauge)):
                problems.append(f"{name} must be a gauge")
            if name.endswith(("_seconds", "_bytes", "_total")):
                problems.append(f"burn rate {name} must be unitless")
    assert not problems, "\n".join(problems)


def test_loadgen_sync_kzg_family_naming_lint():
    """The loadgen / sync-committee / kzg-source label families must
    not drift: every ``scenario`` label value comes from the CLOSED
    scenario registry, every ``kind`` from the model's closed event
    vocabulary, every ``class`` from the VerifyClass enum, and the
    well-known arrival sources are pinned strings (dashboards key on
    ``bls_arrival_rate_per_second{source="kzg"|"sync_committee"}``)."""
    from teku_tpu.crypto import kzg
    from teku_tpu.infra import capacity
    from teku_tpu.infra.metrics import GLOBAL_REGISTRY
    from teku_tpu.loadgen import driver  # noqa: F401 - registers
    from teku_tpu.loadgen.model import EVENT_KINDS
    from teku_tpu.loadgen.scenarios import SCENARIOS
    from teku_tpu.services.admission import CLASS_LABELS, VerifyClass

    # the closed vocabularies themselves
    assert capacity.SOURCE_KZG == kzg.KZG_ARRIVAL_SOURCE == "kzg"
    assert capacity.SOURCE_SYNC_COMMITTEE == "sync_committee"
    assert kzg.kzg_verify_class() is VerifyClass.SYNC_CRITICAL
    assert len(SCENARIOS) >= 4
    assert set(EVENT_KINDS) == {"block", "block_import", "attestation",
                                "aggregate", "sync_message",
                                "sync_contribution", "blob_batch"}

    metrics = GLOBAL_REGISTRY.metrics()
    events = metrics["loadgen_events_total"]
    assert isinstance(events, LabeledCounter)
    assert tuple(events.labelnames) == ("scenario", "kind")
    sheds = metrics["loadgen_sheds_total"]
    assert isinstance(sheds, LabeledCounter)
    assert tuple(sheds.labelnames) == ("scenario", "class")
    dedup = metrics["loadgen_dedup_ratio"]
    assert isinstance(dedup, LabeledGauge)
    assert tuple(dedup.labelnames) == ("scenario",)
    # any series already recorded stays inside the closed sets
    for (scenario, kind), _c in events._items():
        assert scenario in SCENARIOS and kind in EVENT_KINDS
    for (scenario, cls), _c in sheds._items():
        assert scenario in SCENARIOS and cls in CLASS_LABELS
    for (scenario,), _c in dedup._items():
        assert scenario in SCENARIOS

    problems = []
    for name, m in metrics.items():
        if not name.startswith("loadgen_"):
            continue
        if isinstance(m, (Counter, LabeledCounter)) \
                and not name.endswith("_total"):
            problems.append(f"counter {name} must end _total")
        if name.endswith("_total") \
                and not isinstance(m, (Counter, LabeledCounter)):
            problems.append(f"{name} ends _total but is not a counter")
        if isinstance(m, (Gauge, LabeledGauge)) \
                and not name.endswith(("_ratio", "_seconds",
                                       "_per_second")):
            problems.append(f"gauge {name} needs a unit suffix")
        if _DURATION_HINT.search(name) and not name.endswith("_seconds"):
            problems.append(f"duration metric {name} must end _seconds")
    assert not problems, "\n".join(problems)

    # the combined exposition stays structurally valid with the new
    # families declared (HELP/TYPE from scrape 1)
    fams = parse_exposition(GLOBAL_REGISTRY.expose())
    for fam in ("loadgen_events_total", "loadgen_sheds_total",
                "loadgen_dedup_ratio"):
        assert fam in fams and fams[fam]["type"] is not None


def test_dispatch_ledger_family_label_contract():
    """The PR-13 dispatch-ledger families must not drift: the
    padding-waste gauge carries exactly one `stage` label from the
    CLOSED {lane, h2c} set (the lane series keeps the pre-ledger
    unlabeled gauge's semantics), the imbalance gauge is unlabeled,
    and the decision counter's two label vocabularies are both
    closed — {0, pow-2 devices} x the five plan modes.  The ring itself
    is bounded memory."""
    import teku_tpu.ops.provider  # noqa: F401 - registers families
    from teku_tpu.infra import dispatchledger
    from teku_tpu.infra.metrics import GLOBAL_REGISTRY

    metrics = GLOBAL_REGISTRY.metrics()
    waste = metrics["bls_dispatch_padding_waste_ratio"]
    assert isinstance(waste, LabeledGauge)
    assert tuple(waste.labelnames) == ("stage",)
    stages = set(dispatchledger.WASTE_STAGES)
    assert stages == {"lane", "h2c"}
    for key, _child in waste._items():
        assert set(key) <= stages, key
    # both stage series exist from scrape 1 (pre-seeded)
    assert {key[0] for key, _ in waste._items()} == stages

    assert isinstance(metrics["bls_mesh_shard_imbalance_ratio"], Gauge)

    dec = metrics["bls_dispatch_decision_total"]
    assert isinstance(dec, LabeledCounter)
    assert tuple(dec.labelnames) == ("mesh", "plan_mode")
    pow2_vocab = {"0"} | {str(1 << i) for i in range(1, 9)}
    for (mesh, plan_mode), _child in dec._items():
        assert mesh in pow2_vocab, mesh
        assert plan_mode in dispatchledger.PLAN_MODES, plan_mode
    # the label folder can only emit the documented plan modes, on
    # arbitrary (including garbage) inputs
    for mode in (None, "latency", "throughput", "garbage", 3):
        for level in (None, 0, 1, 2, 9, "x"):
            assert dispatchledger.plan_mode_label(mode, level) \
                in dispatchledger.PLAN_MODES

    # bounded ring memory: capacity records retained, seq keeps counting
    led = dispatchledger.DispatchLedger(capacity=4,
                                        registry=MetricsRegistry())
    for _ in range(9):
        led.record({"lanes": 1,
                    "waste": {"lane": {"real": 1, "padded": 2}},
                    "msm": {"path": "ladder"}, "mesh": {"devices": 0},
                    "admission": {}})
    assert len(led.snapshot()) == 4
    assert led.recorded_total == 9

    # exposition stays structurally valid with the families declared
    fams = parse_exposition(GLOBAL_REGISTRY.expose())
    for fam in ("bls_dispatch_padding_waste_ratio",
                "bls_mesh_shard_imbalance_ratio",
                "bls_dispatch_decision_total"):
        assert fam in fams and fams[fam]["type"] is not None

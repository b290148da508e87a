"""Capture hardening of the host bench entry point (bench.py): the
headline assembly and the contention-aware run filter. These mechanisms
decide the number of record, so they get their own tests."""

import json
import sys

import pytest

sys.path.insert(0, __file__.rsplit('/tests/', 1)[0])

import bench  # noqa: E402


def test_main_emits_headline_line(monkeypatch, capsys):
    """main()'s JSON assembly runs end-to-end with stubbed measurement — a
    NameError in the final print would otherwise only surface in the driver's
    once-per-round capture, losing the round's number."""
    import types

    import petastorm_tpu.tools.throughput as tp

    monkeypatch.setattr(bench, '_prebuild_native', lambda: None)
    monkeypatch.setattr(bench, '_ensure_dataset', lambda url, **kw: None)
    monkeypatch.setattr(bench, '_warm', lambda url: None)
    monkeypatch.setattr(bench, '_spin_ms', lambda: 250.0)
    monkeypatch.setattr(tp, 'reader_throughput',
                        lambda *a, **k: types.SimpleNamespace(samples_per_second=5000.0))
    bench.main()
    lines = capsys.readouterr().out.strip().splitlines()
    rec = json.loads(lines[-1])
    assert rec['metric'] == 'hello_world_reader_throughput'
    assert rec['value'] == 5000.0
    # identical runs on an identical-speed host: normalized == raw
    assert rec['value_spin_normalized'] == 5000.0
    assert len(rec['runs']) == 7 and len(rec['cpu_shares']) == 7
    assert len(rec['spin_ms']) == 7 and rec['host_speed_spread'] == 0.0
    assert rec['spread'] == 0.0 and rec['excluded_mad_outliers'] == []
    assert 'duty' not in rec  # the chip path is chip_smoke.py, not this capture
    # default capture runs at counters level: no critical-path block
    assert rec['critical_path'] is None
    # compression knob defaults: snappy store, sweep only on request, and the
    # predicate-share key is always present so round-over-round diffs line up
    assert rec['compression'] == 'snappy'
    assert rec['compression_sweep'] is None
    assert 'fused_predicate_share' in rec


def test_critical_path_section_spans_level():
    """At spans level the headline embeds the causal-tracing summary; below
    it the block stays None (no half-filled attributions)."""
    from petastorm_tpu import observability as obs
    saved = obs.current_config()
    obs.configure('spans')
    try:
        obs.get_ring().clear()
        with obs.mint_trace('feedc0de', 3):
            with obs.stage('ventilate', cat='ventilator'):
                pass
        section = bench._critical_path_section('spans')
        assert section['traced_batches'] == 1
        assert section['slowest'][0]['trace'] == 'feedc0de:3'
        assert bench._critical_path_section('counters') is None
        assert bench._critical_path_section(None) is None
    finally:
        obs.configure(saved)
        obs.get_ring().clear()


def test_select_runs_excludes_contended():
    """A run whose CPU share shows it lost the core is excluded from the
    median (the BENCH_r04 bimodality: two of five runs ~10% low)."""
    runs = [(5600.0, 0.98), (5000.0, 0.86), (5650.0, 0.97),
            (5580.0, 0.975), (5610.0, 0.98), (5590.0, 0.97), (5620.0, 0.96)]
    value, spread, spread_all, excluded, mad_excluded = bench._select_runs(runs)
    assert excluded == [5000.0]
    assert mad_excluded == []
    assert value == pytest.approx(5605.0)  # median of the 6 clean runs
    assert spread < 0.02 < spread_all


def test_select_runs_mad_outlier_excluded():
    """A share-clean run far off the cluster (host-speed dip mid-run) is a
    MAD outlier: excluded from the median WITH the exclusion on record."""
    runs = [(5600.0, 0.98), (5650.0, 0.97), (4300.0, 0.975),  # dip, clean share
            (5580.0, 0.975), (5610.0, 0.98), (5590.0, 0.97), (5620.0, 0.96)]
    value, spread, spread_all, excluded, mad_excluded = bench._select_runs(runs)
    assert excluded == []
    assert mad_excluded == [4300.0]
    assert value == pytest.approx(5605.0)
    assert spread < 0.02
    assert spread_all == pytest.approx((5650.0 - 4300.0) / 5600.0, rel=1e-3)


def test_select_runs_zero_dispersion_keeps_all():
    """mad == 0 (near-identical runs) means no dispersion — the filter must
    not treat it as infinite confidence and evict the one run that differs by
    a hundredth (review r5 regression)."""
    runs = [(5000.0, 0.98)] * 6 + [(5000.01, 0.98)]
    value, spread, spread_all, excluded, mad_excluded = bench._select_runs(runs)
    assert mad_excluded == [] and excluded == []
    assert value == pytest.approx(5000.0)
    assert spread == pytest.approx(spread_all)


def test_select_runs_contended_capture_reports_all():
    """Fewer than 4 clean runs -> no filtering: the whole capture was
    contended and the report must say so rather than cherry-pick."""
    runs = [(5600.0, 0.98), (5000.0, 0.80), (4900.0, 0.79),
            (4800.0, 0.81), (5100.0, 0.82), (4950.0, 0.80), (5050.0, 0.83)]
    value, spread, spread_all, excluded, mad_excluded = bench._select_runs(runs)
    assert excluded == [] and mad_excluded == []
    assert value == pytest.approx(5000.0)
    assert spread == spread_all


def test_fused_predicate_share():
    """The headline's predicate-share metric: pred batches over all fused
    batches; None when nothing fused (no fabricated 0.0 from a dead capture)."""
    assert bench._fused_predicate_share({}) is None
    assert bench._fused_predicate_share({'fused_batches_total': 8}) == 0.0
    assert bench._fused_predicate_share(
        {'fused_batches_total': 8, 'fused_pred_batches_total': 2}) == 0.25


# ---------------------------------------------------------------------------
# Spin-normalized headline (the CPU-wander remedy)
# ---------------------------------------------------------------------------

def test_spin_normalization_cancels_host_speed_wander():
    """A run that is 20% slow ONLY because the host was 20% slow (spin probe
    20% higher) normalizes back to the cluster: rate × spin / median(spin)."""
    rates = [5000.0, 5000.0, 5000.0 / 1.2, 5000.0, 5000.0]
    spins = [250.0, 250.0, 250.0 * 1.2, 250.0, 250.0]
    norm = bench._spin_normalized(rates, spins)
    assert norm == pytest.approx(5000.0)
    # raw median is also 5000 here, but the slow run's NORMALIZED value is
    # exactly restored — verify the per-run formula directly
    per_run = [r * s / 250.0 for r, s in zip(rates, spins)]
    assert per_run[2] == pytest.approx(5000.0)


def test_spin_normalization_uniform_host_is_identity():
    rates = [4000.0, 4100.0, 4200.0]
    spins = [300.0, 300.0, 300.0]
    assert bench._spin_normalized(rates, spins) == pytest.approx(4100.0)


def test_spin_normalization_degenerate_inputs():
    assert bench._spin_normalized([], []) is None
    assert bench._spin_normalized([1.0], [1.0, 2.0]) is None
    # zero spins (clock glitch): fall back to the raw median, not a crash
    assert bench._spin_normalized([10.0, 20.0, 30.0], [0.0, 0.0, 0.0]) == 20.0

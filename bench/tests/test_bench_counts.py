"""``bench/counts.py`` against hand counts on a tiny GraphSAGE and GCN."""
import pytest

from bench import counts

SAGE = dict(model="graphsage", feat_dim=4, hidden=6, n_classes=3,
            n_layers=2, dtype="bfloat16")


def test_fullgraph_sage_by_hand():
    c = counts.fullgraph(SAGE, n=5, edges=8)
    # layer 1 aggregates the 4-wide input (6 > 4), layer 2 the 3-wide
    # h @ w_neigh; bf16 rows and weights, int32 ids, one written row each
    assert c["agg_calls"] == [
        {"flops": 2 * 8 * 4, "bytes": 8 * (4 * 2 + 4 + 2) + 5 * 4 * 2},
        {"flops": 2 * 8 * 3, "bytes": 8 * (3 * 2 + 4 + 2) + 5 * 3 * 2}]
    fwd = (64 + 2 * (2 * 5 * 4 * 6)) + (48 + 2 * (2 * 5 * 6 * 3))
    assert c["model_flops"] == 3 * fwd


def test_fullgraph_gcn_fuses_the_self_term():
    gcn = dict(SAGE, model="gcn", dtype="float32")
    c = counts.fullgraph(gcn, n=5, edges=8)
    # self row and weight per output row; f32 everywhere
    assert c["agg_calls"][0] == {
        "flops": 2 * 8 * 4 + 2 * 5 * 4,
        "bytes": 8 * (16 + 4 + 4) + 5 * 16 + 5 * (16 + 4)}
    fwd = (64 + 40 + 240) + (48 + 30 + 180)
    assert c["model_flops"] == 3 * fwd


def test_sampled_sage_by_hand():
    c = counts.sampled(SAGE, batch=2, fanouts=(3, 2), edges=(5, 9))
    # hop rows 2, 6, 12; float32 rows; layer 1 aggregates hops 0 and 1,
    # layer 2 hop 0 only
    assert c["agg_calls"] == [
        {"flops": 2 * 5 * 4, "bytes": 5 * (16 + 4 + 4) + 2 * 16},
        {"flops": 2 * 9 * 4, "bytes": 9 * (16 + 4 + 4) + 6 * 16},
        {"flops": 2 * 5 * 6, "bytes": 5 * (24 + 4 + 4) + 2 * 24}]
    fwd = (40 + 2 * 96) + (72 + 2 * 288) + (60 + 2 * 72)
    assert c["model_flops"] == 3 * fwd


def test_least_seconds_takes_the_binding_peak():
    peaks = {"flops_per_s": 100.0, "bytes_per_s": 10.0}
    calls = [{"flops": 1000.0, "bytes": 50.0}, {"flops": 100.0, "bytes": 50.0}]
    assert counts.least_seconds(calls, peaks) == pytest.approx(10.0 + 5.0)


def test_peaks_table_refuses_an_unknown_device(tmp_path):
    p = tmp_path / "peaks.json"
    p.write_text('{"TPU v5 lite": {"flops_per_s": 1, "bytes_per_s": 2}}')
    assert counts.peaks(str(p), "TPU v5 lite")["bytes_per_s"] == 2
    with pytest.raises(KeyError):
        counts.peaks(str(p), "TPU v9")


def test_other_models_have_no_counts():
    with pytest.raises(ValueError):
        counts.fullgraph(dict(SAGE, model="gat"), n=5, edges=8)

import json

import pytest

from bench import spec

BENCH = spec.benchmark()


def test_every_name_in_the_benchmark_is_found():
    for w in BENCH["workloads"]:
        cell = spec.cell(w["name"], BENCH)
        assert cell.config["name"] == cell.config_name
        assert cell.traffic["clients"] >= 1
        for m in cell.per_layer:
            assert callable(spec.metric_module(m["name"]).compute)
        spec.reference_module(cell.config["reference"])
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}


def test_config_files_hold_the_configuration_as_run():
    for c in BENCH["configs"]:
        config = spec.load_json(spec.ROOT / c["file"])
        assert config["source"] == c["source"]
        assert config["reduced"] == c["reduced"]
        for key in c["reduced"]:
            assert config["published"][key] != config[key]
        assert config["serving"]["weights"] in ("dense", "stream", "fused")
        assert config["check"]["max_logit_gap"] > 0


def test_per_layer_metrics_move_a_metric_their_cells_report():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    cells = {w["name"] for w in BENCH["workloads"]}
    for m in BENCH["per_layer"]:
        moved = e2e[m["moves"]]
        assert set(m["workloads"]) <= set(moved.get("workloads", cells))
    for w in cells:
        cell = spec.cell(w, BENCH)
        assert len(cell.end_to_end) >= 2 and cell.per_layer


def test_unknown_names_are_errors():
    with pytest.raises(spec.SpecError):
        spec.cell("no-such-cell", BENCH)
    with pytest.raises(spec.SpecError):
        spec.metric_module("no-such-metric")
    with pytest.raises(spec.SpecError):
        spec.peaks("TPU v99")


def test_peaks_name_their_source():
    table = json.loads((spec.BENCH_DIR / "peaks.json").read_text())
    assert "Google Cloud" in table["source"]
    v5e = spec.peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9


def test_harness_maps_published_keys_to_the_program():
    from bench.harness import arch_config

    cfg = arch_config(spec.cell("qwen3-32b.stream.chat", BENCH).config)
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
            cfg.d_ff, cfg.vocab_size, cfg.qk_norm) == \
        (5120, 64, 8, 128, 25600, 151936, True)
    cfg = arch_config(spec.cell("stablelm-3b.fused.chat", BENCH).config)
    assert (cfg.head_dim_(), cfg.norm_eps, cfg.rope_theta) == \
        (80, 1e-5, 1e4)


def test_prefill_metric_reads_the_mean_admission_to_first_token():
    import types

    run = types.SimpleNamespace(prefill_s=[0.1, 0.3])
    assert spec.metric_module("engine.prefill_ms").compute(run) == \
        pytest.approx(200.0)
    assert spec.metric_module("engine.prefill_ms").compute(
        types.SimpleNamespace(prefill_s=[])) is None


def test_ttft_metric_reads_the_p90_of_the_windows_first_tokens():
    import types

    metric = spec.metric_module("engine.ttft_p90_ms")
    window = types.SimpleNamespace(ttft_s=[0.01 * i for i in range(1, 11)])
    run = types.SimpleNamespace(window=window)
    assert metric.compute(run) == pytest.approx(90.0)
    window.ttft_s = []
    assert metric.compute(run) is None

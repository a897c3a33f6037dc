"""Configurations, traffic mixes, cell rates, references and metric
readers are found by the names in BENCHMARK.json, from files alone."""
import harness


def test_every_cell_finds_its_files():
    bench = harness.load_json(harness.ROOT / "BENCHMARK.json")
    for w in bench["workloads"]:
        cell = harness.load_cell(w["name"])
        assert cell.settings["rate"] > 0
        assert cell.traffic["preroll_s"] > 0 and cell.traffic["warm_prompt_lengths"]
        ref = harness.load_reference(cell.config["reference"])
        dims = ref.Dims.from_config(cell.config)
        assert dims.n_layers == cell.config["num_hidden_layers"]
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        assert cell.per_layer


def test_every_metric_has_a_reader_and_every_listed_cell_exists():
    bench = harness.load_json(harness.ROOT / "BENCHMARK.json")
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["per_layer"]:
        assert callable(harness.load_reader(m["name"]))
        assert set(m.get("workloads", cells)) <= cells
    for m in bench["end_to_end"]:
        assert set(m.get("workloads", cells)) <= cells


def test_every_per_layer_metric_moves_an_end_to_end_metric():
    bench = harness.load_json(harness.ROOT / "BENCHMARK.json")
    end_to_end = {m["name"] for m in bench["end_to_end"]}
    assert all(m["moves"] in end_to_end for m in bench["per_layer"])


def test_config_files_lie_under_paths_and_name_their_source():
    bench = harness.load_json(harness.ROOT / "BENCHMARK.json")
    for c in bench["configs"]:
        assert c["file"].startswith(tuple(p + "/" for p in bench["paths"]))
        assert harness.load_json(harness.ROOT / c["file"])["source"] == c["source"]


def test_program_config_matches_the_registry_where_the_file_agrees():
    from repro.configs import get_config

    cfg = harness.load_json(harness.BENCH / "configs" / "smollm-360m-ideal.json")
    got, reg = harness.program_config(cfg), get_config(cfg["registry"])
    for f in ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim", "d_ff",
              "vocab_size", "rope_theta", "tie_embeddings", "stages"):
        assert getattr(got, f) == getattr(reg, f), f
    # the published epsilon, where the registry has another
    assert got.norm_eps == cfg["rms_norm_eps"] == 1e-5


def test_the_chip_a_configuration_names_is_the_one_programmed():
    assert harness.device_config({"chip": {"kind": "ideal"}}, 5) is None
    dev = harness.device_config({"chip": {"kind": "noisy", "device": {
        "sigma": 0.05, "p_stuck_on": 2e-3, "p_stuck_off": 2e-3, "spare_cols": 4}}}, 2**33 + 5)
    assert (dev.sigma, dev.p_stuck_on, dev.spare_cols, dev.seed) == (0.05, 2e-3, 4, 5)

"""The benchmark harness's own tests under tier-1: every case of
``chipbench/tests/test_spec.py`` (which stays where a ``benchmark`` PR
put it; a ``model_config`` PR may not move a benchmark file), two of
them expected to fail (below), and the
OLMoE configuration's: its arithmetic's hand-worked cases, its file's
keys against the catalog's, and a ``SpecError`` for a file with a key
missing."""

import json

import pytest

from chipbench import spec as spec_mod
from chipbench.tests import test_spec as harness
from chipbench.tests.test_spec import (  # noqa: F401  (collected here)
    CELLS,
    scoped,
    test_a_call_under_two_scopes_or_two_stacks_counts_for_neither,
    test_a_cell_finds_its_reference_and_arithmetic_by_its_configurations_keys,
    test_a_configuration_without_a_contract_key_is_a_spec_error,
    test_a_module_under_another_root_is_the_one_that_is_loaded,
    test_a_named_module_that_is_not_there_is_a_spec_error,
    test_gpt2_arithmetic_gives_its_hand_worked_numbers,
    test_no_file_of_the_harness_names_a_blocks_module_or_size_key,
    test_the_committed_files_give_the_exchanged_vector_and_the_flops,
    test_the_flash_readers_read_the_attn_family_alone,
    test_the_hand_made_trace_reduces_to_its_hand_checked_numbers,
    test_the_recorded_fixture_keeps_its_numbers_and_books_no_family,
    test_the_references_new_signature_is_the_old_call_to_the_bit,
)

OLMOE_CELL = "olmoe-l1-ps1w-su1"

# Two cases of that file state what held while every cell was GPT-2's
# (one vocabulary, five scopes) and fail since a second block is in
# BENCHMARK.json; the file is the benchmark's and not a model_config
# PR's to edit (CHANGES.md and PERF.md section 7, PR 26).  They run here
# as they are, expected to fail for that reason and loudly if they stop;
# the two cases after them state the same intents for every cell.
GPT2_ONLY = ("states what held while every cell was GPT-2's; "
             "chipbench/tests/test_spec.py is a benchmark PR's to edit")


@pytest.mark.xfail(strict=True, reason=GPT2_ONLY)
def test_the_vocabulary_reaches_the_program_through_the_launcher():
    harness.test_the_vocabulary_reaches_the_program_through_the_launcher()


@pytest.mark.xfail(strict=True, reason=GPT2_ONLY)
def test_scopes_come_from_the_cells_configuration():
    harness.test_scopes_come_from_the_cells_configuration()


def test_every_cells_vocabulary_reaches_the_program_through_the_launcher():
    from chipbench import run as runner

    want = {"gpt2": 50257, "olmoe": 50304, "mellum": 12288, "lfm2_moe": 8192,
            "ouro": 49152, "joyai_llm_flash": 16160, "kimi_linear": 20480,
            "KeyeVL2": 18992, "sdar_moe": 18992, "afmoe": 25024,
            "nemotron_h": 16384, "qwen3_next": 18992,
            "granitemoehybrid": 12544}
    for name in CELLS:
        cell = spec_mod.load_cell(name)
        cfg = runner.launch_config(cell, seed=5)
        assert cfg.lm_vocab == cell.config["vocab_size"] \
            == want[cell.config["model_type"]]


def test_scopes_come_from_every_committed_configuration():
    from chipbench.layers import spantree

    cell = spec_mod.load_cell("c111m-local")
    cell.config["scopes"] = ["router", "experts"]
    assert spantree.model_scopes({"cell": cell}) == ["router", "experts"]
    # a run that names no cell: every scope a committed configuration
    # lists, in order of first mention
    assert spantree.model_scopes({}) == [
        "embed", "attn", "mlp", "head_loss", "update", "router", "dispatch",
        "experts", "attn_window", "conv", "conv_mix", "exit_gate", "mla_proj",
        "shared_expert", "kda_proj", "kda_scan", "kda_out", "index", "noise",
        "attn_gate", "bias_rule", "dense_mlp", "ssm_proj", "ssm_conv",
        "ssd_scan", "ssm_norm", "gdn_proj", "gdn_conv", "gdn_scan",
        "gdn_out"]


def olmoe_cases():
    cell = spec_mod.load_cell(OLMOE_CELL)
    return cell.arithmetic().hand_worked()


@pytest.mark.parametrize("what,got,want", olmoe_cases(),
                         ids=[c[0] for c in olmoe_cases()])
def test_olmoe_arithmetic_by_hand(what, got, want):
    assert got == want, what


def test_olmoe_arithmetic_on_the_committed_file():
    cell = spec_mod.load_cell(OLMOE_CELL)
    arithmetic, c = cell.arithmetic(), cell.config
    assert arithmetic.param_count(c) == 625_616_896
    assert arithmetic.train_flops_per_token(c) == 1_071_919_104
    families = arithmetic.kernels(c, 2)
    assert set(families) == {"attn", "experts"}
    assert families["experts"]["flops"] \
        == arithmetic.experts_cost(c, 2)["flops"]
    # the sparse form needs 8/64 of the dense form's expert FLOPs
    dense = 18.0 * 2 * 4096 * c["num_experts"] * 2048 * 1024
    assert arithmetic.experts_cost(c, 2)["flops"] == dense * 8 / 64


# The catalog's entry (model-configs guide, architectures.jsonl,
# OLMoE-1B-7B-0125-Instruct): every number under its own key; only
# ``reduced`` may differ.
CATALOG = {
    "attention_bias": False, "clip_qkv": None, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 1024,
    "max_position_embeddings": 4096, "model_type": "olmoe",
    "norm_topk_prob": False, "num_attention_heads": 16, "num_experts": 64,
    "num_experts_per_tok": 8, "num_hidden_layers": 16,
    "num_key_value_heads": 16, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "tie_word_embeddings": False, "vocab_size": 50304,
}


def test_olmoe_file_has_the_catalogs_keys_and_cuts_depth_alone():
    cell = spec_mod.load_cell(OLMOE_CELL)
    config = cell.config
    differ = sorted(k for k, v in CATALOG.items() if config.get(k, "?") != v)
    assert differ == config["reduced"] == ["num_hidden_layers"]
    assert config["published"] == {"num_hidden_layers": 16}
    entry = next(c for c in cell.bench["configs"]
                 if c["name"] == cell.config_name)
    assert entry["reduced"] == ["num_hidden_layers"]
    assert set(config["tiny"]) <= set(CATALOG)  # the block's own keys
    assert cell.chips == 1
    assert {"embed", "attn", "router", "dispatch", "experts", "head_loss",
            "update"} == set(config["scopes"])


@pytest.mark.parametrize("missing", ["reference", "arithmetic", "scopes",
                                     "tiny", "launcher_from"])
def test_an_olmoe_file_with_a_key_missing_is_a_spec_error(tmp_path, missing):
    base = spec_mod.load_cell(OLMOE_CELL)
    entry = next(c for c in base.bench["configs"]
                 if c["name"] == base.config_name)
    bench = {**base.bench, "configs": [entry],
             "workloads": [w for w in base.bench["workloads"]
                           if w["name"] == OLMOE_CELL]}
    config = {k: v for k, v in base.config.items() if k != missing}
    (tmp_path / "chipbench" / "configs").mkdir(parents=True)
    (tmp_path / "chipbench" / "traffic").mkdir()
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    (tmp_path / entry["file"]).write_text(json.dumps(config))
    (tmp_path / "chipbench" / "traffic" / "ps1w-su1-s4k.json").write_text(
        json.dumps(base.traffic))
    with pytest.raises(spec_mod.SpecError, match=missing):
        spec_mod.load_cell(OLMOE_CELL, root=tmp_path)


def test_a_launcher_switch_for_a_size_the_file_lacks_is_a_spec_error():
    from chipbench import run as runner

    cell = spec_mod.load_cell(OLMOE_CELL)
    del cell.config["num_experts"]
    with pytest.raises(spec_mod.SpecError, match="num_experts"):
        runner.launch_config(cell, 1)


def test_the_new_readers_find_nothing_in_a_run_without_the_block():
    """What the parent's traced run hands them: a cell whose
    configuration lists no such scope, no merged trace: None, no raise."""
    cell = spec_mod.load_cell("c111m-ps1w-su1")
    run = {"cell": cell, "reduction": {"step_module": "jit_loss"},
           "obs_trace": None, "peaks": None, "results": {},
           "summary": {"worker_ranks": [1], "window": [0.0, 1.0]}}
    for name in ("experts_ms_per_step", "experts_roofline",
                 "dispatch_ms_per_step", "expert_load_max_over_mean"):
        reader = spec_mod.load_reader(cell.root, cell.bench, name)
        assert reader is not None and reader(dict(run)) is None


# -- the Mellum configuration (PR 30) ---------------------------------------------

MELLUM_CELL = "mellum2-l4e8-local"


def mellum_cases():
    return spec_mod.load_cell(MELLUM_CELL).arithmetic().hand_worked()


@pytest.mark.parametrize("what,got,want", mellum_cases(),
                         ids=[c[0] for c in mellum_cases()])
def test_mellum_arithmetic_by_hand(what, got, want):
    assert got == want, what


def test_mellum_file_has_the_catalogs_keys_and_states_its_cuts():
    """Every number of the catalog's entry under its own key (the
    model-configs guide's ``architectures.jsonl``, read where it is
    installed; the hand-copied numbers below where it is not), nested
    groups whole; only ``reduced`` differs, no width among it, each cut
    at the guide's floor with the published count beside it."""
    import pathlib

    cell = spec_mod.load_cell(MELLUM_CELL)
    config = cell.config
    catalog = {
        "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
        "hidden_size": 2304, "intermediate_size": 7168,
        "max_position_embeddings": 131072, "max_window_layers": 0,
        "model_type": "mellum", "moe_intermediate_size": 896,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts": 64, "num_experts_per_tok": 8,
        "num_hidden_layers": 28, "num_key_value_heads": 4,
        "rms_norm_eps": 1e-06, "sliding_window": 1024,
        "tie_word_embeddings": False, "vocab_size": 98304,
        "use_sliding_window": True}
    path = pathlib.Path(
        "/opt/skills/guides/model-configs/architectures.jsonl")
    if path.exists():
        rows = [json.loads(line) for line in path.read_text().splitlines()]
        entry = next(r for r in rows if r["name"].startswith("Mellum2-12B"))
        assert {k: v for k, v in entry["config"].items()
                if not isinstance(v, (list, dict))} == catalog
        assert entry["source_url"] == config["source"]
        catalog = entry["config"]
    differ = sorted(k for k, v in catalog.items() if config.get(k, "?") != v)
    assert differ == sorted(config["reduced"]) == [
        "num_experts", "num_hidden_layers", "vocab_size"]
    assert config["published"] == {k: catalog[k] for k in config["reduced"]}
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"]) == (4, 8, 98304 // 8)   # the floors
    assert config["router_experts"] == 64   # the router keeps its width
    entry = next(c for c in cell.bench["configs"]
                 if c["name"] == cell.config_name)
    assert sorted(entry["reduced"]) == differ
    assert cell.chips == 1
    assert {"embed", "attn", "attn_window", "router", "dispatch", "experts",
            "head_loss", "update"} == set(config["scopes"])
    assert all(key in config for key in config["launcher_from"].values())
    assert all(key in config for key in config["tiny"])


def test_mellums_readers_find_nothing_in_a_run_without_the_block():
    """What the parent's traced run hands them: a cell whose
    configuration lists no such scope and holds no share, a program that
    recorded no such counter, no merged trace: None, no raise."""
    cell = spec_mod.load_cell("olmoe-l1-ps1w-su1")
    run = {"cell": cell, "reduction": {"step_module": "jit_loss"},
           "obs_trace": None, "peaks": None, "results": {},
           "summary": {"worker_ranks": [1], "window": [0.0, 1.0]}}
    for name in ("attn_window_ms_per_step", "attn_window_roofline",
                 "held_experts_ms_per_step", "held_experts_roofline",
                 "held_rows_share_pct", "compact_dispatch_pct"):
        reader = spec_mod.load_reader(cell.root, cell.bench, name)
        assert reader is not None and reader(dict(run)) is None


def test_mellums_readers_read_a_hand_made_run(monkeypatch):
    """The five readers on a reduction and a span tree made by hand:
    three rounds with shares of 10%, 15% and 15% a layer, of which the
    device trace holds the last two; 30 ms of Mosaic calls under
    ``attn_window`` and 20 under ``experts`` over two runs of the
    step."""
    from chipbench import flops
    from chipbench.layers import held_rows_share_pct, spantree

    cell = spec_mod.load_cell(MELLUM_CELL)

    class Round:
        def __init__(self, k, share):
            self.args = {"round": k, "moe_held_rows_share": [share] * 4}

    class Tree:
        def rounds(self):
            return [Round(7, 0.10), Round(8, 0.15), Round(9, 0.15)]

    monkeypatch.setattr(spantree, "xplane_path", lambda run: "a.xplane.pb")
    monkeypatch.setattr(spantree, "anchors",
                        lambda path: [(8, 0.0, 0.0), (9, 1.0, 1.0)])

    run = {"cell": cell, "peaks": flops.load_peaks("TPU v5 lite"),
           spantree.CACHE_KEY: Tree(),
           "reduction": {"step_module_runs": 2, "mosaic_by_scope": {
               "attn_window": (18, 0.060), "experts": (96, 0.040)}}}

    def read(name):
        return spec_mod.load_reader(cell.root, cell.bench, name)(run)

    assert held_rows_share_pct.rounds_mean(run) == pytest.approx(
        [0.10, 0.15, 0.15])
    assert held_rows_share_pct.rounds_mean(run, {7}) == pytest.approx([0.10])
    assert read("held_rows_share_pct") == pytest.approx(15.0)
    assert read("attn_window_ms_per_step") == pytest.approx(30.0)
    kernels = cell.arithmetic().kernels(cell.config, 1)
    window = kernels["attn_window"]
    assert read("attn_window_roofline") == pytest.approx(
        100 * window["flops"] / 197e12 / 0.030)
    # the experts' rows at 15 / 12.5 of the uniform expectation, by the
    # traced rounds
    cost = cell.arithmetic().experts_cost(cell.config, 1)
    assert read("held_experts_roofline") == pytest.approx(
        100 * max(cost["flops"] * 1.2 / 197e12,
                  (cost["bytes"] + 0.2 * cost["rows_bytes"]) / 819e9) / 0.020)


# -- the LFM2 configuration (PR 32) -----------------------------------------------

LFM2_CELL = "lfm2-l5e8-local"
NEMOTRON_CELL = "nemotron3-l9e8-local"
GRANITE_CELL = "granite4h-l10-local"
QWEN3NEXT_CELL = "qwen3next-l4e32-local"


def lfm2_cases():
    return spec_mod.load_cell(LFM2_CELL).arithmetic().hand_worked()


@pytest.mark.parametrize("what,got,want", lfm2_cases(),
                         ids=[c[0] for c in lfm2_cases()])
def test_lfm2_arithmetic_by_hand(what, got, want):
    assert got == want, what


def test_lfm2_file_has_the_catalogs_keys_and_states_its_cuts():
    """Every number of the catalog's entry under its own key (the
    model-configs guide's ``architectures.jsonl``, read where it is
    installed; the hand-copied numbers below where it is not), nested
    groups and ``layer_types`` whole; only ``reduced`` differs, no width
    among it, each cut at the guide's floor with the published count
    beside it, and a key for which layers are here."""
    import pathlib

    cell = spec_mod.load_cell(LFM2_CELL)
    config = cell.config
    catalog = {
        "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
        "intermediate_size": 11776, "max_position_embeddings": 128000,
        "model_type": "lfm2_moe", "moe_intermediate_size": 1536,
        "norm_eps": 1e-05, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_dense_layers": 2,
        "num_experts": 64, "num_experts_per_tok": 4,
        "num_hidden_layers": 40, "num_key_value_heads": 8,
        "routed_scaling_factor": 1, "use_expert_bias": True,
        "vocab_size": 65536}
    path = pathlib.Path(
        "/opt/skills/guides/model-configs/architectures.jsonl")
    if path.exists():
        rows = [json.loads(line) for line in path.read_text().splitlines()]
        entry = next(r for r in rows if r["name"] == "LFM2-24B-A2B")
        assert {k: v for k, v in entry["config"].items()
                if not isinstance(v, (list, dict))} == catalog
        assert entry["source_url"] == config["source"]
        catalog = entry["config"]
        assert config["layer_types"] == catalog["layer_types"]
        assert config["rope_parameters"] == catalog["rope_parameters"]
    differ = sorted(k for k, v in catalog.items() if config.get(k, "?") != v)
    assert differ == sorted(config["reduced"]) == [
        "num_experts", "num_hidden_layers", "vocab_size"]
    assert config["published"] == {k: catalog[k] for k in config["reduced"]}
    # the floors: the dense layer and a whole period of four after it,
    # 8 experts, an eighth of the vocabulary
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"]) == (5, 8, 65536 // 8)
    assert config["router_experts"] == 64   # the router keeps its width
    assert (config["first_layer"], config["dense_layers_here"]) == (1, 1)
    entry = next(c for c in cell.bench["configs"]
                 if c["name"] == cell.config_name)
    assert sorted(entry["reduced"]) == differ
    assert entry["source"] == config["source"]
    assert cell.chips == 1
    assert ["embed", "conv", "conv_mix", "attn", "mlp", "router", "dispatch",
            "experts", "head_loss", "update"] == config["scopes"]
    assert all(key in config for key in config["launcher_from"].values())
    assert all(key in config for key in config["tiny"])
    assert len(config["assumed"]) >= 6 and config["deployment"]


def test_lfm2s_readers_find_nothing_in_a_run_without_the_block():
    """What the parent's traced run hands them: a cell whose
    configuration lists no such scope, whose arithmetic has no such
    cost, a program that recorded no such counter, no merged trace:
    None, no raise."""
    for name in ("mellum2-l4e8-local", "c111m-local"):
        cell = spec_mod.load_cell(name)
        run = {"cell": cell, "reduction": {"step_module": "jit__lambda"},
               "obs_trace": None, "peaks": None, "results": {},
               "summary": {"worker_ranks": [0], "window": [0.0, 1.0]}}
        for metric in ("conv_ms_per_step", "conv_mix_ms_per_step",
                       "conv_mix_roofline", "router_bias_flips_pct"):
            reader = spec_mod.load_reader(cell.root, cell.bench, metric)
            assert reader is not None and reader(dict(run)) is None


@pytest.mark.parametrize("rounds,want", [
    ([[1.0] * 4] * 3, 100.0),                      # every layer, every step
    ([[1.0] * 4, [1.0, 0.0, 1.0, 1.0], [0.0] * 4], 100.0 * 7 / 12),
    ([[]] * 3, None),                   # a block that holds everything
    ([None] * 3, None)])                # a program that has no such counter
def test_compact_dispatch_pct_is_the_mean_over_layers_and_rounds(rounds,
                                                                 want):
    """In both cells that list it: the share of (layer, step) pairs that
    went through the window; None where the rounds carry no such arg, as
    the parent's do."""
    from chipbench.layers import spantree

    class Round:
        def __init__(self, compact):
            self.args = {"moe_held_rows_share": [0.125] * 4}
            if compact is not None:
                self.args["moe_compact_share"] = compact

    class Tree:
        def rounds(self):
            return [Round(r) for r in rounds]

    for name in (MELLUM_CELL, LFM2_CELL):
        cell = spec_mod.load_cell(name)
        assert "compact_dispatch_pct" in [
            m["name"] for m in cell.metrics("per_layer")]
        got = spec_mod.load_reader(cell.root, cell.bench,
                                   "compact_dispatch_pct")(
            {"cell": cell, spantree.CACHE_KEY: Tree()})
        assert got == (want if want is None else pytest.approx(want))


def test_lfm2s_readers_read_a_hand_made_run(monkeypatch):
    """The four readers, and the five appended ones, on a scope table
    and a span tree made by hand: 30 ms under ``conv`` and 12 under
    ``conv_mix`` a step; three rounds whose four sparse layers flip 2%,
    4% and 4% of their choices."""
    from chipbench import flops
    from chipbench.layers import spantree

    cell = spec_mod.load_cell(LFM2_CELL)

    class Round:
        def __init__(self, k, flips):
            self.args = {"round": k, "moe_bias_flips_share": [flips] * 4,
                         "moe_held_rows_share": [0.125] * 4,
                         "moe_load_max_over_mean": [1.5, 1.4, 1.9, 1.6]}

    class Tree:
        def rounds(self):
            return [Round(7, 0.02), Round(8, 0.04), Round(9, 0.04)]

    table = {"step": 250.0, "conv": 30.0, "conv_mix": 12.0, "router": 5.0,
             "dispatch": 35.0, "experts": 40.0, "head_loss": 11.0}
    monkeypatch.setattr(spantree, "scope_ms_per_step", lambda run: table)
    monkeypatch.setattr(spantree, "xplane_path", lambda run: None)
    run = {"cell": cell, "peaks": flops.load_peaks("TPU v5 lite"),
           spantree.CACHE_KEY: Tree(),
           "reduction": {"step_module_runs": 2, "mosaic_by_scope": {
               "attn": (4, 0.040), "experts": (96, 0.060)}}}

    def read(name):
        return spec_mod.load_reader(cell.root, cell.bench, name)(run)

    assert read("conv_ms_per_step") == pytest.approx(42.0)
    assert read("conv_mix_ms_per_step") == pytest.approx(12.0)
    cost = cell.arithmetic().conv_mix_cost(cell.config, 1)
    assert cost["bytes"] == 4 * 4 * (8192 * 22528 + 18_432)
    assert read("conv_mix_roofline") == pytest.approx(
        100 * cost["bytes"] / 819e9 / 0.012)
    assert read("router_bias_flips_pct") == pytest.approx(4.0)
    assert read("dispatch_ms_per_step") == pytest.approx(40.0)
    assert read("held_experts_ms_per_step") == pytest.approx(40.0)
    assert read("held_rows_share_pct") == pytest.approx(12.5)
    assert read("expert_load_max_over_mean") == pytest.approx(1.9)
    assert read("flash_ms_per_step") == pytest.approx(20.0)
    experts = cell.arithmetic().experts_cost(cell.config, 1)
    assert read("held_experts_roofline") == pytest.approx(
        100 * max(experts["flops"] / 197e12, experts["bytes"] / 819e9)
        / 0.030)
    # every metric the cell lists has a reader
    for metric in cell.metrics("per_layer"):
        assert spec_mod.load_reader(cell.root, cell.bench,
                                    metric["name"]) is not None


def test_the_parent_fails_the_new_cell_at_once():
    """A checkout without the cell's entry ends the command with a
    ``SpecError``, before any gang: what the driver's trial of the new
    cell on the parent needs."""
    bench = spec_mod.load_bench()
    names = [w["name"] for w in bench["workloads"]]
    assert names[-10:] == [LFM2_CELL, OURO_CELL, JOYAI_CELL, KIMI_CELL,
                           KEYE_CELL, SDAR_CELL, TRINITY_CELL,
                           NEMOTRON_CELL, QWEN3NEXT_CELL, GRANITE_CELL] \
        and len(names) == 15
    for missing in ("lfm2-l5e8-locals", "ouro-l6-locals",
                    "joyai-l5e8-locals", "kimi-linear-l5e8-locals",
                    "keye-l6e8-locals", "sdar-l6e8-locals",
                    "trinity-l5e8-locals", "nemotron3-l9e8-locals",
                    "qwen3next-l4e32-locals", "granite4h-l10-locals"):
        with pytest.raises(spec_mod.SpecError, match="no workload"):
            spec_mod.load_cell(missing)


# -- the Ouro configuration (PR 36) -----------------------------------------------

OURO_CELL = "ouro-l6-local"
OURO_METRICS = ("exit_gate_ms_per_step", "exit_step_mean",
                "loop_loss_drop_nats")


def test_ouro_file_has_the_catalogs_keys_and_cuts_depth_alone():
    """Every number of the catalog's entry under its own key (the
    model-configs guide's ``architectures.jsonl``, read where it is
    installed; the hand-copied numbers below where it is not),
    ``layer_types`` whole; only the depth differs, with the published
    count beside it; the vocabulary is whole and no width is cut."""
    import pathlib

    cell = spec_mod.load_cell(OURO_CELL)
    config = cell.config
    catalog = {
        "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 5632, "max_position_embeddings": 65536,
        "max_window_layers": 48, "model_type": "ouro",
        "num_attention_heads": 16, "num_hidden_layers": 48,
        "num_key_value_heads": 16, "rms_norm_eps": 1e-06,
        "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
        "tie_word_embeddings": False, "total_ut_steps": 4,
        "early_exit_threshold": 1, "use_sliding_window": False,
        "vocab_size": 49152}
    path = pathlib.Path(
        "/opt/skills/guides/model-configs/architectures.jsonl")
    if path.exists():
        rows = [json.loads(line) for line in path.read_text().splitlines()]
        entry = next(r for r in rows if r["name"] == "Ouro-2.6B")
        assert {k: v for k, v in entry["config"].items()
                if not isinstance(v, (list, dict))} == catalog
        assert entry["source_url"] == config["source"]
        catalog = entry["config"]
        assert config["layer_types"] == catalog["layer_types"]
    differ = sorted(k for k, v in catalog.items() if config.get(k, "?") != v)
    assert differ == config["reduced"] == ["num_hidden_layers"]
    assert config["published"] == {"num_hidden_layers": 48}
    assert config["num_hidden_layers"] == 6 and len(config["layer_types"]) == 48
    entry = next(c for c in cell.bench["configs"]
                 if c["name"] == cell.config_name)
    assert entry["reduced"] == differ and entry["source"] == config["source"]
    assert (cell.chips, cell.traffic_name) == (1, "local-msgd-s4k-ouro")
    assert (cell.traffic["batch"], cell.traffic["su"],
            cell.traffic["launcher"]["opt"]) == (1, 1, "msgd")
    assert config["train_seq"] == 4096 and config["exit_entropy_beta"] == 0.1
    assert ["embed", "attn", "mlp", "exit_gate", "head_loss",
            "update"] == config["scopes"]
    assert all(key in config for key in config["launcher_from"].values())
    assert all(key in config for key in config["tiny"])
    assert config["tiny"]["total_ut_steps"] >= 2 <= \
        config["tiny"]["num_hidden_layers"]
    assert len(config["assumed"]) >= 6 and config["deployment"]
    assert cell.arithmetic().param_count(config) == 509_661_185


def test_the_launcher_builds_the_looped_block_from_the_cells_files():
    from chipbench import run as runner
    from mpit_tpu.lm.model import build_kw
    from mpit_tpu.train.launch import lm_trainer_cfg

    cell = spec_mod.load_cell(OURO_CELL)
    kw = build_kw(lm_trainer_cfg(runner.launch_config(cell, seed=5)))
    assert (kw["arch"], kw["loop_steps"], kw["exit_beta"]) == ("ouro", 4, 0.1)
    assert kw["exit_bias"] == cell.config["exit_gate_bias_init"] == -4.0
    assert (kw["d_model"], kw["n_heads"], kw["kv_heads"], kw["head_dim"],
            kw["n_layers"], kw["dense_width"], kw["seq_len"], kw["vocab"]) \
        == (2048, 16, 16, 128, 6, 5632, 4096, 49152)
    assert (kw["rope_theta"], kw["norm_eps"]) == (1000000.0, 1e-6)


def test_ouros_mix_keeps_to_the_traffic_its_issue_fixed():
    """ISSUE 36 fixed the mix before any code was written: the rate one
    of three, the budget a fifth to a half of the 85 micro-steps a 51 s
    run makes (whole sequences of 4096), the learning floor half of what
    the seeds learnt by the budget (5.30..5.53 nats), momentum 0.9, and
    the counters of what the loop learns move the loss, not the rate."""
    cell = spec_mod.load_cell(OURO_CELL)
    mix = cell.traffic
    steps, rest = divmod(mix["token_budget"],
                         mix["batch"] * cell.config["train_seq"])
    assert rest == 0 and 17 <= steps <= 42
    assert mix["lr"] in (0.003, 0.01, 0.03)
    assert 2.0 <= mix["min_learning_nats"] <= 2.8
    assert (mix["launcher"]["mom"], mix["warmup_rounds"]) == (0.9, 2)
    moves = {m["name"]: m["moves"] for m in cell.metrics("per_layer")}
    assert moves["exit_step_mean"] == moves["loop_loss_drop_nats"] \
        == "loss_at_budget"
    assert moves["exit_gate_ms_per_step"] == "tokens_per_s"


def test_ouros_readers_find_nothing_in_a_run_without_the_block():
    """What the parent's traced run hands them: a cell whose
    configuration lists no such scope, a program that recorded no such
    counter, no merged trace: None, no raise."""
    for name in ("lfm2-l5e8-local", "c111m-local"):
        cell = spec_mod.load_cell(name)
        run = {"cell": cell, "reduction": {"step_module": "jit__lambda"},
               "obs_trace": None, "peaks": None, "results": {},
               "summary": {"worker_ranks": [0], "window": [0.0, 1.0]}}
        for metric in OURO_METRICS:
            reader = spec_mod.load_reader(cell.root, cell.bench, metric)
            assert reader is not None and reader(dict(run)) is None


def test_ouros_readers_read_a_hand_made_run(monkeypatch):
    """The three readers, and the ten metrics without a ``workloads``
    list that the cell has to report, on a scope table and a span tree
    made by hand: 3 ms under ``exit_gate`` and 80 under ``head_loss`` a
    step; three rounds that leave at 1.9, 2.1 and 2.4 and whose later
    passes buy 0.2, 0.3 and 0.1 nats."""
    from chipbench import flops
    from chipbench.layers import spantree

    cell = spec_mod.load_cell(OURO_CELL)
    listed = [m["name"] for m in cell.metrics("per_layer")]
    assert set(OURO_METRICS) <= set(listed)
    unlisted = [m["name"] for m in cell.bench["per_layer"]
                if "workloads" not in m]
    assert len(unlisted) == 10 and set(unlisted) <= set(listed)

    class Round:
        def __init__(self, k, exit_step, drop):
            self.args = {"round": k, "loop_exit_step_mean": [exit_step],
                         "loop_loss_drop": [drop],
                         "loop_exit_entropy": [1.2]}

    class Tree:
        def rounds(self):
            return [Round(7, 1.9, 0.2), Round(8, 2.4, 0.3),
                    Round(9, 2.1, 0.1)]

    table = {"step": 1000.0, "attn": 400.0, "mlp": 300.0, "exit_gate": 3.0,
             "head_loss": 80.0, "update": 30.0}
    monkeypatch.setattr(spantree, "scope_ms_per_step", lambda run: table)
    monkeypatch.setattr(spantree, "xplane_path", lambda run: None)
    run = {"cell": cell, "peaks": flops.load_peaks("TPU v5 lite"),
           spantree.CACHE_KEY: Tree(),
           "summary": {"tokens_per_s": 4000.0, "worker_ranks": [0]},
           "reduction": {"step_module_runs": 2, "mosaic_by_scope": {
               "attn": (144, 0.400), "update": (2, 0.030)}}}

    def read(name):
        return spec_mod.load_reader(cell.root, cell.bench, name)(run)

    assert read("exit_gate_ms_per_step") == pytest.approx(3.0)
    assert read("exit_step_mean") == pytest.approx(2.1)
    assert read("loop_loss_drop_nats") == pytest.approx(0.2)
    assert read("head_loss_ms_per_step") == pytest.approx(80.0)
    # 72 flash calls a step (24 forward, 24 recomputed, 24 backward)
    assert read("flash_ms_per_step") == pytest.approx(200.0)
    family = cell.arithmetic().kernels(cell.config, 1)["attn"]
    assert read("flash_roofline") == pytest.approx(
        100 * family["flops"] / 197e12 / 0.200)
    assert read("mfu_pct") == pytest.approx(
        100 * 11_022_925_824 * 4000.0 / 197e12)
    for metric in cell.metrics("per_layer"):
        assert spec_mod.load_reader(cell.root, cell.bench,
                                    metric["name"]) is not None


# -- the JoyAI-LLM-Flash configuration (PR 38) -----------------------------------

JOYAI_CELL = "joyai-l5e8-local"
JOYAI_METRICS = ("mla_proj_ms_per_step", "shared_expert_ms_per_step",
                 "mtp_ms_per_step", "mtp_nll_gap_nats")
JOYAI_APPENDED = ("dispatch_ms_per_step", "expert_load_max_over_mean",
                  "held_experts_ms_per_step", "held_experts_roofline",
                  "held_rows_share_pct", "router_bias_flips_pct",
                  "compact_dispatch_pct")


def test_joyai_file_has_the_catalogs_keys_and_the_three_floor_cuts():
    """Every number of the catalog's entry under its own key (the
    model-configs guide's ``architectures.jsonl``, read where it is
    installed; the hand-copied numbers below where it is not); only the
    depth, the experts held and the vocabulary differ, each at the
    guide's floor, with the published counts beside them; no width is
    cut."""
    import pathlib

    cell = spec_mod.load_cell(JOYAI_CELL)
    config = cell.config
    catalog = {
        "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
        "head_dim": 64, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 7168, "kv_lora_rank": 512,
        "max_position_embeddings": 131072, "model_type": "joyai_llm_flash",
        "moe_intermediate_size": 768, "moe_layer_freq": 1, "n_group": 1,
        "n_routed_experts": 256, "n_shared_experts": 1,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts_per_tok": 8, "num_hidden_layers": 40,
        "num_key_value_heads": 32, "num_nextn_predict_layers": 1,
        "q_lora_rank": 1536, "qk_head_dim": 192, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
        "rope_interleave": True, "rope_scaling": None,
        "rope_theta": 32000000, "routed_scaling_factor": 2.5,
        "scoring_func": "sigmoid", "tie_word_embeddings": False,
        "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 128,
        "vocab_size": 129280}
    path = pathlib.Path(
        "/opt/skills/guides/model-configs/architectures.jsonl")
    if path.exists():
        rows = [json.loads(line) for line in path.read_text().splitlines()]
        entry = next(r for r in rows if r["name"] == "JoyAI-LLM-Flash")
        assert entry["config"] == catalog
        assert entry["source_url"] == config["source"]
    assert all(key in config for key in catalog)
    differ = sorted(k for k, v in catalog.items() if config[k] != v)
    assert differ == sorted(config["reduced"]) == [
        "n_routed_experts", "num_hidden_layers", "vocab_size"]
    assert config["published"] == {k: catalog[k] for k in config["reduced"]}
    # the floors: the dense layer and four sparse layers after it (one
    # layer is the period), 8 experts, an eighth of the vocabulary
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (5, 8, 129280 // 8)
    assert config["router_experts"] == 256   # the router keeps its width
    assert config["num_experts"] == config["n_routed_experts"]
    assert (config["train_seq"], config["mtp_loss_weight"]) == (8192, 0.3)
    entry = next(c for c in cell.bench["configs"]
                 if c["name"] == cell.config_name)
    assert sorted(entry["reduced"]) == differ
    assert entry["source"] == config["source"]
    assert (cell.chips, cell.traffic_name) == (1, "local-msgd-s8k-joyai")
    assert ["embed", "mla_proj", "attn", "mlp", "router", "dispatch",
            "experts", "shared_expert", "head_loss",
            "update"] == config["scopes"]
    assert all(key in config for key in config["launcher_from"].values())
    assert all(key in config for key in config["tiny"])
    assert len(config["assumed"]) >= 8 and "32 v5e chips" in \
        config["deployment"]
    assert cell.arithmetic().param_count(config) == 491_697_408


def test_the_launcher_builds_the_latent_block_from_the_cells_files():
    from chipbench import run as runner
    from mpit_tpu.lm.model import build_kw
    from mpit_tpu.train.launch import lm_trainer_cfg

    cell = spec_mod.load_cell(JOYAI_CELL)
    kw = build_kw(lm_trainer_cfg(runner.launch_config(cell, seed=5)))
    assert (kw["arch"], kw["d_model"], kw["n_heads"], kw["n_layers"],
            kw["seq_len"], kw["vocab"]) == ("joyai", 2048, 32, 5, 8192, 16160)
    assert (kw["q_rank"], kw["kv_rank"], kw["qk_nope"], kw["qk_rope"],
            kw["v_head"]) == (1536, 512, 128, 64, 128)
    assert (kw["dense_layers"], kw["dense_width"], kw["n_experts"],
            kw["experts_held"], kw["experts_first"], kw["experts_per_tok"],
            kw["expert_width"], kw["shared_experts"]) \
        == (1, 7168, 256, 8, 0, 8, 768, 1)
    assert (kw["route_scale"], kw["mtp_layers"], kw["mtp_weight"],
            kw["rope_theta"], kw["norm_eps"]) == (2.5, 1, 0.3, 32e6, 1e-6)


def test_joyais_mix_keeps_to_the_traffic_its_issue_fixed():
    """ISSUE 38 fixed the mix before any code was written: the rate one
    of three, the budget a fifth to a half of the micro-steps a 51 s run
    makes (whole sequences of 8192), momentum 0.9, two rounds of
    warm-up, closed loop in one process; the four new metrics and the
    seven appended ones are the cell's, and the MTP gap moves the loss,
    not the rate."""
    cell = spec_mod.load_cell(JOYAI_CELL)
    mix = cell.traffic
    steps, rest = divmod(mix["token_budget"],
                         mix["batch"] * cell.config["train_seq"])
    assert rest == 0 and steps >= 8
    assert mix["lr"] in (0.003, 0.01, 0.03)
    assert (mix["launcher"]["mom"], mix["warmup_rounds"], mix["su"],
            mix["batch"], mix["launcher"]["np"]) == (0.9, 2, 1, 1, 1)
    moves = {m["name"]: m["moves"] for m in cell.metrics("per_layer")}
    assert set(JOYAI_METRICS + JOYAI_APPENDED) <= set(moves)
    assert moves["mtp_nll_gap_nats"] == "loss_at_budget"
    assert {moves[m] for m in JOYAI_METRICS[:3]} == {"tokens_per_s"}
    for metric in cell.bench["per_layer"]:
        if metric["name"] in JOYAI_METRICS[2:]:
            assert metric["workloads"] == [JOYAI_CELL]
        elif metric["name"] in JOYAI_APPENDED + JOYAI_METRICS[:2]:
            # the cells of PR 43, PR 46, PR 51, PR 53, PR 58 and PR 61,
            # which have some of these layers too, follow it
            assert JOYAI_CELL in metric["workloads"][-7:]


def test_joyais_readers_find_nothing_in_a_run_without_the_block():
    """What the parent's traced run hands them: a cell whose
    configuration lists no such scope, a program that recorded no such
    counter, no device trace, no merged trace: None, no raise."""
    for name in ("lfm2-l5e8-local", "c111m-local"):
        cell = spec_mod.load_cell(name)
        run = {"cell": cell, "reduction": {"step_module": "jit__lambda"},
               "obs_trace": None, "peaks": None, "results": {},
               "summary": {"worker_ranks": [0], "window": [0.0, 1.0]}}
        for metric in JOYAI_METRICS:
            reader = spec_mod.load_reader(cell.root, cell.bench, metric)
            assert reader is not None and reader(dict(run)) is None


def test_joyais_readers_read_a_hand_made_run(monkeypatch):
    """The four readers, the seven shared ones and the metrics without a
    ``workloads`` list that the cell has to report, on a scope table, a
    chip's operations and a span tree made by hand."""
    from chipbench import flops
    from chipbench.layers import spantree

    cell = spec_mod.load_cell(JOYAI_CELL)
    listed = [m["name"] for m in cell.metrics("per_layer")]
    unlisted = [m["name"] for m in cell.bench["per_layer"]
                if "workloads" not in m]
    assert len(unlisted) == 10 and set(unlisted) <= set(listed)

    class Round:
        def __init__(self, k, main, mtp):
            self.args = {"round": k, "lm_main_nll": [main],
                         "lm_mtp_nll": [mtp],
                         "moe_held_rows_share": [0.03125] * 5,
                         "moe_load_max_over_mean": [2.0, 2.5, 2.25, 2.0, 3.0],
                         "moe_compact_share": [1.0] * 5,
                         "moe_bias_flips_share": [0.1] * 5}

    class Tree:
        def rounds(self):
            return [Round(7, 3.0, 3.5), Round(8, 2.5, 3.25),
                    Round(9, 2.0, 3.0)]

    table = {"step": 400.0, "mla_proj": 40.0, "attn": 200.0, "mlp": 20.0,
             "router": 6.0, "dispatch": 9.0, "experts": 12.0,
             "shared_expert": 5.0, "head_loss": 30.0, "update": 25.0}
    # two runs of the step's program; of four operations one outside
    # them and three inside, two of these under ``mtp``
    chip = {"plane": "/device:TPU:0", "lo": 0, "hi": 10_000_000,
            "modules": [("jit__lambda(1)", 1_000_000, 2_000_000),
                        ("jit__lambda(1)", 5_000_000, 2_000_000)],
            "ops": [("a", 1_100_000, 300_000), ("b", 1_500_000, 200_000),
                    ("c", 5_100_000, 500_000), ("a", 8_000_000, 900_000)]}
    stacks = {"a": "jit(f)/jvp(JoyaiDecoder)/mtp/mtp_block/attn/dot",
              "b": "jit(f)/jvp(JoyaiDecoder)/JoyaiBlock_1/attn/dot",
              "c": "jit(f)/transpose(jvp(JoyaiDecoder))/mtp/head_loss/dot"}
    monkeypatch.setattr(spantree, "scope_ms_per_step", lambda run: table)
    monkeypatch.setattr(spantree, "xplane_path", lambda run: None)
    monkeypatch.setattr(spantree, "traced_chip", lambda run: chip)
    monkeypatch.setattr(spantree, "op_scopes", lambda path, plane: stacks)
    run = {"cell": cell, "peaks": flops.load_peaks("TPU v5 lite"),
           spantree.CACHE_KEY: Tree(),
           "summary": {"tokens_per_s": 20000.0, "worker_ranks": [0]},
           "reduction": {"step_module": "jit__lambda", "step_module_runs": 2,
                         "mosaic_by_scope": {
                             "attn": (36, 0.360), "experts": (90, 0.020),
                             "update": (2, 0.030)}}}

    def read(name):
        return spec_mod.load_reader(cell.root, cell.bench, name)(run)

    assert read("mla_proj_ms_per_step") == pytest.approx(40.0)
    assert read("shared_expert_ms_per_step") == pytest.approx(5.0)
    # (0.3 + 0.5) ms over two runs of the step
    assert read("mtp_ms_per_step") == pytest.approx(0.4)
    assert read("mtp_nll_gap_nats") == pytest.approx(0.75)
    assert read("dispatch_ms_per_step") == pytest.approx(15.0)
    assert read("held_experts_ms_per_step") == pytest.approx(12.0)
    assert read("held_rows_share_pct") == pytest.approx(3.125)
    assert read("expert_load_max_over_mean") == pytest.approx(3.0)
    assert read("router_bias_flips_pct") == pytest.approx(10.0)
    assert read("compact_dispatch_pct") == pytest.approx(100.0)
    assert read("head_loss_ms_per_step") == pytest.approx(30.0)
    assert read("flash_ms_per_step") == pytest.approx(180.0)
    family = cell.arithmetic().kernels(cell.config, 1)["attn"]
    assert read("flash_roofline") == pytest.approx(
        100 * family["flops"] / 197e12 / 0.180)
    experts = cell.arithmetic().experts_cost(cell.config, 1)
    assert read("held_experts_roofline") == pytest.approx(
        100 * max(experts["flops"] / 197e12, experts["bytes"] / 819e9)
        / 0.010)
    assert read("mfu_pct") == pytest.approx(
        100 * 3_362_967_552 * 20000.0 / 197e12)
    for metric in cell.metrics("per_layer"):
        assert spec_mod.load_reader(cell.root, cell.bench,
                                    metric["name"]) is not None


def joyai_cases():
    return spec_mod.load_cell(JOYAI_CELL).arithmetic().hand_worked()


@pytest.mark.parametrize("what,got,want", joyai_cases(),
                         ids=[c[0] for c in joyai_cases()])
def test_joyai_arithmetic_by_hand_through_the_cell(what, got, want):
    assert got == want, what

# -- the Kimi-Linear configuration (PR 43) ----------------------------------------

KIMI_CELL = "kimi-linear-l5e8-local"
KIMI_METRICS = ("kda_ms_per_step", "kda_scan_ms_per_step",
                "kda_scan_roofline", "kda_decay_mean")
KIMI_APPENDED = JOYAI_APPENDED + ("mla_proj_ms_per_step",
                                  "shared_expert_ms_per_step")


def test_kimi_file_has_the_catalogs_keys_and_the_floor_cuts():
    """Every key of the catalog's entry under its own name (the
    model-configs guide's ``architectures.jsonl``, read where it is
    installed; the hand-copied values below where it is not); only the
    depth, the experts held, the vocabulary and, with the depth, the two
    lists of layer numbers inside ``linear_attn_config`` differ, each at
    the guide's floor, with the published values beside them; no width
    is cut, inside the group or outside."""
    import pathlib

    cell = spec_mod.load_cell(KIMI_CELL)
    config = cell.config
    catalog = {
        "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu",
        "hidden_size": 2304, "intermediate_size": 9216, "kv_lora_rank": 512,
        "linear_attn_config": {
            "full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
            "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18,
                           19, 21, 22, 23, 25, 26],
            "num_heads": 32, "short_conv_kernel_size": 4},
        "mla_use_nope": True, "model_max_length": 1048576,
        "model_type": "kimi_linear", "moe_intermediate_size": 1024,
        "moe_layer_freq": 1, "moe_renormalize": True,
        "moe_router_activation_func": "sigmoid", "num_attention_heads": 32,
        "num_expert_group": 1, "num_experts": 256,
        "num_experts_per_token": 8, "num_hidden_layers": 27,
        "num_key_value_heads": 32, "num_nextn_predict_layers": 0,
        "num_shared_experts": 1, "q_lora_rank": None,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
        "routed_scaling_factor": 2.446, "tie_word_embeddings": False,
        "topk_group": 1, "use_grouped_topk": True, "v_head_dim": 128,
        "vocab_size": 163840}
    path = pathlib.Path(
        "/opt/skills/guides/model-configs/architectures.jsonl")
    if path.exists():
        rows = [json.loads(line) for line in path.read_text().splitlines()]
        entry = next(r for r in rows
                     if r["name"] == "Kimi-Linear-48B-A3B-Instruct")
        assert entry["config"] == catalog
        assert entry["source_url"] == config["source"]
    assert all(key in config for key in catalog)
    differ = sorted(k for k, v in catalog.items() if config[k] != v)
    assert differ == sorted(config["reduced"]) == [
        "linear_attn_config", "num_experts", "num_hidden_layers",
        "vocab_size"]
    assert config["published"] == {k: catalog[k] for k in config["reduced"]}
    # inside the group the widths stand and the lists are the published
    # ones cut to the layers held
    group, whole = config["linear_attn_config"], catalog["linear_attn_config"]
    assert {k: group[k] for k in ("head_dim", "num_heads",
                                  "short_conv_kernel_size")} \
        == {k: whole[k] for k in ("head_dim", "num_heads",
                                  "short_conv_kernel_size")}
    held = range(1, config["num_hidden_layers"] + 1)
    for name in ("kda_layers", "full_attn_layers"):
        assert group[name] == [n for n in whole[name] if n in held]
    # the floors: the dense layer and the four that follow (a whole
    # period among them), 8 experts, an eighth of the vocabulary
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"]) == (5, 8, 163840 // 8)
    assert group["kda_layers"] == [1, 2, 3, 5] and \
        group["full_attn_layers"] == [4]
    assert config["router_experts"] == 256   # the router keeps its width
    assert (config["train_seq"], config["kda_chunk"]) == (8192, 64)
    entry = next(c for c in cell.bench["configs"]
                 if c["name"] == cell.config_name)
    assert sorted(entry["reduced"]) == differ
    assert entry["source"] == config["source"]
    assert (cell.chips, cell.traffic_name) == (1, "local-msgd-s8k-kimi")
    assert ["embed", "kda_proj", "kda_scan", "kda_out", "mla_proj", "attn",
            "mlp", "router", "dispatch", "experts", "shared_expert",
            "head_loss", "update"] == config["scopes"]
    assert all(key in config for key in config["launcher_from"].values())
    assert all(key in config for key in config["tiny"])
    assert len(config["assumed"]) >= 8 and "32 v5e chips" in \
        config["deployment"]
    assert cell.arithmetic().param_count(config) == 602_434_432


def test_the_launcher_builds_the_hybrid_block_from_the_cells_files():
    from chipbench import run as runner
    from mpit_tpu.lm.model import build_kw
    from mpit_tpu.train.launch import lm_trainer_cfg

    cell = spec_mod.load_cell(KIMI_CELL)
    kw = build_kw(lm_trainer_cfg(runner.launch_config(cell, seed=5)))
    assert (kw["arch"], kw["d_model"], kw["n_heads"], kw["n_layers"],
            kw["seq_len"], kw["vocab"]) == ("kimi", 2304, 32, 5, 8192, 20480)
    assert (kw["layer_types"], kw["kda_heads"], kw["kda_head_dim"],
            kw["conv_kernel"]) == ("kda,kda,kda,full_attention,kda", 32,
                                   128, 4)
    assert (kw["q_rank"], kw["kv_rank"], kw["qk_nope"], kw["qk_rope"],
            kw["v_head"], kw["rope_theta"]) == (0, 512, 128, 64, 128, 0.0)
    assert (kw["dense_layers"], kw["dense_width"], kw["n_experts"],
            kw["experts_held"], kw["experts_first"], kw["experts_per_tok"],
            kw["expert_width"], kw["shared_experts"]) \
        == (1, 9216, 256, 8, 0, 8, 1024, 1)
    assert (kw["route_scale"], kw["norm_eps"]) == (2.446, 1e-5)


def test_kimis_mix_keeps_to_the_traffic_its_issue_fixed():
    """ISSUE 43 fixed the mix before any code was written: the rate one
    of three, the budget a whole number of micro-steps (whole sequences
    of 8192, or of 4096 by the one permitted departure), momentum 0.9,
    two rounds of warm-up, closed loop in one process; the four new
    metrics and the nine appended ones are the cell's, and the decay's
    mean moves the loss, not the rate."""
    cell = spec_mod.load_cell(KIMI_CELL)
    mix = cell.traffic
    assert cell.config["train_seq"] in (8192, 4096)
    steps, rest = divmod(mix["token_budget"],
                         mix["batch"] * cell.config["train_seq"])
    assert rest == 0 and steps >= 8
    assert mix["lr"] in (0.003, 0.01, 0.03)
    assert (mix["launcher"]["mom"], mix["warmup_rounds"], mix["su"],
            mix["batch"], mix["launcher"]["np"]) == (0.9, 2, 1, 1, 1)
    moves = {m["name"]: m["moves"] for m in cell.metrics("per_layer")}
    assert set(KIMI_METRICS + KIMI_APPENDED) <= set(moves)
    assert moves["kda_decay_mean"] == "loss_at_budget"
    assert {moves[m] for m in KIMI_METRICS[:3]} == {"tokens_per_s"}
    layers = {m["name"]: m["layer"] for m in cell.bench["per_layer"]}
    assert layers["kda_scan_roofline"] == layers["flash_roofline"]
    assert layers["kda_ms_per_step"] == layers["mla_proj_ms_per_step"]
    for metric in cell.bench["per_layer"]:
        if metric["name"] in KIMI_METRICS:
            assert metric["workloads"] == [KIMI_CELL]
        elif metric["name"] in KIMI_APPENDED:
            # the cells of PR 46, PR 51 and PR 53 follow it where they
            # have the layer
            assert KIMI_CELL in metric["workloads"][-6:]
        elif "workloads" in metric:
            assert KIMI_CELL not in metric["workloads"], metric["name"]


def test_kimis_readers_find_nothing_in_a_run_without_the_block():
    """What the parent's traced run hands them: a cell whose
    configuration lists no such scope, a program that recorded no such
    counter, no device trace, no merged trace: None, no raise."""
    for name in ("joyai-l5e8-local", "c111m-local"):
        cell = spec_mod.load_cell(name)
        run = {"cell": cell, "reduction": {"step_module": "jit__lambda"},
               "obs_trace": None, "peaks": None, "results": {},
               "summary": {"worker_ranks": [0], "window": [0.0, 1.0]}}
        for metric in KIMI_METRICS:
            reader = spec_mod.load_reader(cell.root, cell.bench, metric)
            assert reader is not None and reader(dict(run)) is None


def test_kimis_readers_read_a_hand_made_run(monkeypatch):
    """The four readers, the nine shared ones and the metrics without a
    ``workloads`` list that the cell has to report, on a scope table and
    a span tree made by hand."""
    from chipbench import flops
    from chipbench.layers import spantree

    cell = spec_mod.load_cell(KIMI_CELL)
    listed = [m["name"] for m in cell.metrics("per_layer")]
    unlisted = [m["name"] for m in cell.bench["per_layer"]
                if "workloads" not in m]
    assert len(unlisted) == 10 and set(unlisted) <= set(listed)

    class Round:
        def __init__(self, k, decay):
            self.args = {"round": k, "lm_kda_decay_mean": decay,
                         "moe_held_rows_share": [0.03125] * 4,
                         "moe_load_max_over_mean": [2.0, 2.5, 2.25, 3.0],
                         "moe_compact_share": [1.0] * 4,
                         "moe_bias_flips_share": [0.1] * 4}

    class Tree:
        def rounds(self):
            return [Round(7, [0.8, 0.9, 0.8, 0.9]),
                    Round(8, [0.7, 0.9, 0.8, 0.8]),
                    Round(9, [0.6, 0.8, 0.8, 0.6])]

    table = {"step": 600.0, "kda_proj": 90.0, "kda_scan": 120.0,
             "kda_out": 30.0, "mla_proj": 8.0, "attn": 60.0, "mlp": 20.0,
             "router": 6.0, "dispatch": 9.0, "experts": 12.0,
             "shared_expert": 5.0, "head_loss": 30.0, "update": 25.0}
    monkeypatch.setattr(spantree, "scope_ms_per_step", lambda run: table)
    monkeypatch.setattr(spantree, "xplane_path", lambda run: None)
    run = {"cell": cell, "peaks": flops.load_peaks("TPU v5 lite"),
           spantree.CACHE_KEY: Tree(),
           "summary": {"tokens_per_s": 12000.0, "worker_ranks": [0]},
           "reduction": {"step_module": "jit__lambda", "step_module_runs": 2,
                         "mosaic_by_scope": {
                             "attn": (6, 0.080), "experts": (72, 0.020),
                             "update": (2, 0.030)}}}

    def read(name):
        return spec_mod.load_reader(cell.root, cell.bench, name)(run)

    assert read("kda_ms_per_step") == pytest.approx(240.0)
    assert read("kda_scan_ms_per_step") == pytest.approx(120.0)
    cost = cell.arithmetic().kda_scan_cost(cell.config, 1)
    assert cost["bytes"] / 819e9 > cost["flops"] / 197e12   # memory binds
    assert read("kda_scan_roofline") == pytest.approx(
        100 * cost["bytes"] / 819e9 / 0.120)
    assert read("kda_decay_mean") == pytest.approx(0.8)
    assert read("mla_proj_ms_per_step") == pytest.approx(8.0)
    assert read("shared_expert_ms_per_step") == pytest.approx(5.0)
    assert read("dispatch_ms_per_step") == pytest.approx(15.0)
    assert read("held_experts_ms_per_step") == pytest.approx(12.0)
    assert read("held_rows_share_pct") == pytest.approx(3.125)
    assert read("expert_load_max_over_mean") == pytest.approx(3.0)
    assert read("router_bias_flips_pct") == pytest.approx(10.0)
    assert read("compact_dispatch_pct") == pytest.approx(100.0)
    assert read("head_loss_ms_per_step") == pytest.approx(30.0)
    assert read("flash_ms_per_step") == pytest.approx(40.0)
    family = cell.arithmetic().kernels(cell.config, 1)["attn"]
    assert read("flash_roofline") == pytest.approx(
        100 * family["flops"] / 197e12 / 0.040)
    experts = cell.arithmetic().experts_cost(cell.config, 1)
    assert read("held_experts_roofline") == pytest.approx(
        100 * max(experts["flops"] / 197e12, experts["bytes"] / 819e9)
        / 0.010)
    assert read("mfu_pct") == pytest.approx(
        100 * 2_318_727_168 * 12000.0 / 197e12)
    for metric in cell.metrics("per_layer"):
        assert spec_mod.load_reader(cell.root, cell.bench,
                                    metric["name"]) is not None


def kimi_cases():
    return spec_mod.load_cell(KIMI_CELL).arithmetic().hand_worked()


@pytest.mark.parametrize("what,got,want", kimi_cases(),
                         ids=[c[0] for c in kimi_cases()])
def test_kimi_arithmetic_by_hand_through_the_cell(what, got, want):
    assert got == want, what


# -- the Keye configuration (PR 46) -----------------------------------------------

KEYE_CELL = "keye-l6e8-local"
KEYE_METRICS = ("dsa_ms_per_step", "dsa_index_ms_per_step",
                "dsa_index_roofline", "dsa_kept_pct",
                "dsa_window_overlap_pct")
KEYE_APPENDED = ("dispatch_ms_per_step", "expert_load_max_over_mean",
                 "held_experts_ms_per_step", "held_experts_roofline",
                 "held_rows_share_pct", "compact_dispatch_pct")


def test_keye_file_has_the_catalogs_keys_and_the_floor_cuts():
    """Every key of the catalog's entry under its own name (the
    model-configs guide's ``architectures.jsonl``, read where it is
    installed; the hand-copied values below where it is not); only the
    depth, the experts held and the vocabulary differ, each at the
    guide's floor, with the published values beside them; no width is
    cut, inside ``sa_config`` or outside."""
    import pathlib

    cell = spec_mod.load_cell(KEYE_CELL)
    config = cell.config
    catalog = {
        "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 6144, "max_position_embeddings": 262144,
        "max_window_layers": 48, "mlp_only_layers": [],
        "model_type": "KeyeVL2", "moe_intermediate_size": 768,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts": 128, "num_experts_per_tok": 8,
        "num_hidden_layers": 48, "num_key_value_heads": 4,
        "num_local_experts": 128, "rms_norm_eps": 1e-06,
        "rope_scaling": {"mrope_section": [16, 24, 24],
                         "rope_type": "default", "type": "default"},
        "rope_theta": 10000000,
        "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                      "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                      "q_chunk_size": 512, "topk": 2048},
        "sliding_window": None, "tie_word_embeddings": False,
        "use_sliding_window": False, "vocab_size": 151936}
    path = pathlib.Path(
        "/opt/skills/guides/model-configs/architectures.jsonl")
    if path.exists():
        rows = [json.loads(line) for line in path.read_text().splitlines()]
        entry = next(r for r in rows if r["name"] == "Keye-VL-2.0-30B-A3B")
        assert entry["config"] == catalog
        assert entry["source_url"] == config["source"]
    assert all(key in config for key in catalog)
    differ = sorted(k for k, v in catalog.items() if config[k] != v)
    assert differ == sorted(config["reduced"]) == [
        "num_experts", "num_hidden_layers", "vocab_size"]
    assert config["published"] == {k: catalog[k] for k in config["reduced"]}
    # the floors: six layers (one is the period), 8 experts, an eighth
    # of the vocabulary
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"]) == (6, 8, 151936 // 8)
    assert config["router_experts"] == 128   # the router keeps its width
    assert config["train_seq"] == 8192
    sa = config["sa_config"]
    assert (config["index_heads"], config["index_head_dim"],
            config["index_topk"]) == (sa["indexer_num_heads"],
                                      sa["indexer_head_dim"], sa["topk"])
    entry = next(c for c in cell.bench["configs"]
                 if c["name"] == cell.config_name)
    assert sorted(entry["reduced"]) == differ
    assert entry["source"] == config["source"]
    assert (cell.chips, cell.traffic_name) == (1, "local-msgd-s8k-keye")
    assert ["embed", "index", "attn", "router", "dispatch", "experts",
            "head_loss", "update"] == config["scopes"]
    assert all(key in config for key in config["launcher_from"].values())
    assert all(key in config for key in config["tiny"])
    assert len(config["assumed"]) >= 8 and "16 v5e chips" in \
        config["deployment"]
    assert cell.arithmetic().param_count(config) == 432_697_600


def test_the_launcher_builds_the_selecting_block_from_the_cells_files():
    from chipbench import run as runner
    from mpit_tpu.lm.model import build_kw
    from mpit_tpu.train.launch import lm_trainer_cfg

    cell = spec_mod.load_cell(KEYE_CELL)
    kw = build_kw(lm_trainer_cfg(runner.launch_config(cell, seed=5)))
    assert (kw["arch"], kw["d_model"], kw["n_heads"], kw["kv_heads"],
            kw["head_dim"], kw["n_layers"], kw["seq_len"], kw["vocab"]) \
        == ("keye", 2048, 32, 4, 128, 6, 8192, 18992)
    assert (kw["index_heads"], kw["index_head_dim"], kw["index_topk"]) \
        == (16, 64, 2048)
    assert (kw["n_experts"], kw["experts_held"], kw["experts_first"],
            kw["experts_per_tok"], kw["expert_width"]) == (128, 8, 0, 8, 768)
    assert (kw["rope_theta"], kw["norm_eps"]) == (1e7, 1e-6)


def test_keyes_mix_keeps_to_the_traffic_its_issue_fixed():
    """ISSUE 46 fixed the mix before any code was written: the rate one
    of three, the budget a whole number of micro-steps of 8192, momentum
    0.9, two rounds of warm-up, closed loop in one process; the five new
    metrics and the six appended ones are the cell's, and the
    selection's two counters move the loss, not the rate."""
    cell = spec_mod.load_cell(KEYE_CELL)
    mix = cell.traffic
    assert cell.config["train_seq"] == 8192
    steps, rest = divmod(mix["token_budget"],
                         mix["batch"] * cell.config["train_seq"])
    assert rest == 0 and steps >= 8
    assert mix["lr"] in (0.003, 0.01, 0.03)
    assert (mix["launcher"]["mom"], mix["warmup_rounds"], mix["su"],
            mix["batch"], mix["launcher"]["np"],
            mix["launcher"]["lm_use_flash"]) == (0.9, 2, 1, 1, 1, 1)
    moves = {m["name"]: m["moves"] for m in cell.metrics("per_layer")}
    assert set(KEYE_METRICS + KEYE_APPENDED) <= set(moves)
    assert {moves[m] for m in KEYE_METRICS[3:]} == {"loss_at_budget"}
    assert {moves[m] for m in KEYE_METRICS[:3]} == {"tokens_per_s"}
    layers = {m["name"]: m["layer"] for m in cell.bench["per_layer"]}
    assert layers["dsa_index_roofline"] == layers["flash_roofline"]
    assert layers["dsa_ms_per_step"] == layers["mla_proj_ms_per_step"]
    for metric in cell.bench["per_layer"]:
        if metric["name"] in KEYE_METRICS:
            assert metric["workloads"] == [KEYE_CELL]
        elif metric["name"] in KEYE_APPENDED:
            # the cells of PR 51 and PR 53 follow it
            assert KEYE_CELL in metric["workloads"][-5:]
        elif "workloads" in metric:
            assert KEYE_CELL not in metric["workloads"], metric["name"]
    # added together and in order (later PRs' entries follow them)
    keye = [m for m in cell.bench["per_layer"] if m["name"] in KEYE_METRICS]
    at = cell.bench["per_layer"].index(keye[0])
    assert cell.bench["per_layer"][at:at + 5] == keye
    # (PR 51's and PR 53's configurations and cells follow them)
    assert (cell.bench["configs"][-6]["name"],
            cell.bench["workloads"][-6]["name"]) == (cell.config_name,
                                                     KEYE_CELL)


def test_keyes_readers_find_nothing_in_a_run_without_the_block():
    """What the parent's traced run hands them: a cell whose
    configuration lists no such scope, a program that recorded no such
    counter, no device trace, no merged trace: None, no raise."""
    for name in ("mellum2-l4e8-local", "c111m-local"):
        cell = spec_mod.load_cell(name)
        run = {"cell": cell, "reduction": {"step_module": "jit__lambda"},
               "obs_trace": None, "peaks": None, "results": {},
               "summary": {"worker_ranks": [0], "window": [0.0, 1.0]}}
        for metric in KEYE_METRICS:
            reader = spec_mod.load_reader(cell.root, cell.bench, metric)
            assert reader is not None and reader(dict(run)) is None


def test_keyes_readers_read_a_hand_made_run(monkeypatch):
    """The five readers, the six shared ones and the metrics without a
    ``workloads`` list that the cell has to report, on a scope table and
    a span tree made by hand."""
    from chipbench import flops
    from chipbench.layers import spantree

    cell = spec_mod.load_cell(KEYE_CELL)
    listed = [m["name"] for m in cell.metrics("per_layer")]
    unlisted = [m["name"] for m in cell.bench["per_layer"]
                if "workloads" not in m]
    assert len(unlisted) == 10 and set(unlisted) <= set(listed)

    class Round:
        def __init__(self, k, overlap):
            self.args = {"round": k, "lm_dsa_kept_share": [0.4375] * 6,
                         "lm_dsa_window_overlap": overlap,
                         "moe_held_rows_share": [0.0625] * 6,
                         "moe_load_max_over_mean": [2.0, 2.5, 2.25, 3.0, 2.0,
                                                    2.0],
                         "moe_compact_share": [1.0] * 6}

    class Tree:
        def rounds(self):
            return [Round(7, [0.5] * 6), Round(8, [0.4, 0.6] * 3),
                    Round(9, [0.3] * 6)]

    table = {"step": 500.0, "index": 120.0, "attn": 180.0, "router": 6.0,
             "dispatch": 9.0, "experts": 12.0, "head_loss": 30.0,
             "update": 25.0}
    monkeypatch.setattr(spantree, "scope_ms_per_step", lambda run: table)
    monkeypatch.setattr(spantree, "xplane_path", lambda run: None)
    run = {"cell": cell, "peaks": flops.load_peaks("TPU v5 lite"),
           spantree.CACHE_KEY: Tree(),
           "summary": {"tokens_per_s": 15000.0, "worker_ranks": [0]},
           "reduction": {"step_module": "jit__lambda", "step_module_runs": 2,
                         "mosaic_by_scope": {
                             "attn": (36, 0.240), "experts": (72, 0.020),
                             "update": (2, 0.030)}}}

    def read(name):
        return spec_mod.load_reader(cell.root, cell.bench, name)(run)

    assert read("dsa_ms_per_step") == pytest.approx(300.0)
    assert read("dsa_index_ms_per_step") == pytest.approx(120.0)
    cost = cell.arithmetic().index_cost(cell.config, 1)
    assert cost["flops"] / 197e12 > cost["bytes"] / 819e9   # compute binds
    assert read("dsa_index_roofline") == pytest.approx(
        100 * cost["flops"] / 197e12 / 0.120)
    assert read("dsa_kept_pct") == pytest.approx(43.75)
    assert read("dsa_window_overlap_pct") == pytest.approx(50.0)
    assert read("dispatch_ms_per_step") == pytest.approx(15.0)
    assert read("held_experts_ms_per_step") == pytest.approx(12.0)
    assert read("held_rows_share_pct") == pytest.approx(6.25)
    assert read("expert_load_max_over_mean") == pytest.approx(3.0)
    assert read("compact_dispatch_pct") == pytest.approx(100.0)
    assert read("head_loss_ms_per_step") == pytest.approx(30.0)
    assert read("flash_ms_per_step") == pytest.approx(120.0)
    family = cell.arithmetic().kernels(cell.config, 1)["attn"]
    assert read("flash_roofline") == pytest.approx(
        100 * family["flops"] / 197e12 / 0.120)
    experts = cell.arithmetic().experts_cost(cell.config, 1)
    assert read("held_experts_roofline") == pytest.approx(
        100 * max(experts["flops"] / 197e12, experts["bytes"] / 819e9)
        / 0.010)
    assert read("mfu_pct") == pytest.approx(
        100 * 1_613_211_648 * 15000.0 / 197e12)
    for metric in cell.metrics("per_layer"):
        assert spec_mod.load_reader(cell.root, cell.bench,
                                    metric["name"]) is not None


def keye_cases():
    return spec_mod.load_cell(KEYE_CELL).arithmetic().hand_worked()


@pytest.mark.parametrize("what,got,want", keye_cases(),
                         ids=[c[0] for c in keye_cases()])
def test_keye_arithmetic_by_hand_through_the_cell(what, got, want):
    assert got == want, what


# -- the SDAR configuration (PR 51) -----------------------------------------------

SDAR_CELL = "sdar-l6e8-local"
SDAR_METRICS = ("blockdiff_dead_tiles_pct", "noise_ms_per_step",
                "diff_masked_pct", "diff_nll_gap_nats")


def test_sdar_file_has_the_catalogs_keys_and_the_floor_cuts():
    """Every key of the catalog's entry under its own name (the
    model-configs guide's ``architectures.jsonl``, read where it is
    installed; ``tests/test_sdar.py`` holds the hand-copied values);
    only the depth, the experts held and the vocabulary differ, each at
    the guide's floor, with the published values beside them; no width
    is cut; what the catalog does not give is stated as assumed."""
    import pathlib

    cell = spec_mod.load_cell(SDAR_CELL)
    config = cell.config
    path = pathlib.Path(
        "/opt/skills/guides/model-configs/architectures.jsonl")
    if path.exists():
        rows = [json.loads(line) for line in path.read_text().splitlines()]
        entry = next(r for r in rows if r["name"] == "SDAR-30B-A3B-Chat")
        catalog = entry["config"]
        assert entry["source_url"] == config["source"]
        assert all(key in config for key in catalog)
        differ = sorted(k for k, v in catalog.items() if config[k] != v)
        assert differ == sorted(config["reduced"])
        assert config["published"] == {k: catalog[k]
                                       for k in config["reduced"]}
        for missing in entry["not_given"]:
            assert missing.split()[0] in " ".join(config["assumed"])
    assert sorted(config["reduced"]) == [
        "num_experts", "num_hidden_layers", "vocab_size"]
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"]) == (6, 8, 151936 // 8)
    assert (config["hidden_size"], config["num_attention_heads"],
            config["num_key_value_heads"], config["head_dim"],
            config["moe_intermediate_size"], config["num_experts_per_tok"],
            config["rope_theta"]) == (2048, 32, 4, 128, 768, 8, 1000000)
    assert config["router_experts"] == 128   # the router keeps its width
    assert (config["train_seq"], config["block_length"],
            config["mask_token_id"]) == (4096, 4, 18991)
    entry = next(c for c in cell.bench["configs"]
                 if c["name"] == cell.config_name)
    assert sorted(entry["reduced"]) == sorted(config["reduced"])
    assert entry["source"] == config["source"]
    assert (cell.chips, cell.traffic_name) == (1, "local-msgd-s4k-sdar")
    assert ["embed", "noise", "attn", "router", "dispatch", "experts",
            "head_loss", "update"] == config["scopes"]
    assert all(key in config for key in config["launcher_from"].values())
    assert all(key in config for key in config["tiny"])
    assert len(config["assumed"]) >= 8 and "16 v5e chips" in \
        config["deployment"]
    assert cell.arithmetic().param_count(config) == 419_130_880
    assert cell.arithmetic().live_pairs(4096, 4) == 16_793_600
    assert cell.reference().LOSS_TOL_NATS > 0 < cell.reference().GRAD_REL_TOL


def test_the_launcher_builds_the_diffusion_block_from_the_cells_files():
    from chipbench import run as runner
    from mpit_tpu.lm.model import build_kw
    from mpit_tpu.train.launch import lm_trainer_cfg

    cell = spec_mod.load_cell(SDAR_CELL)
    kw = build_kw(lm_trainer_cfg(runner.launch_config(cell, seed=5)))
    assert (kw["arch"], kw["d_model"], kw["n_heads"], kw["kv_heads"],
            kw["head_dim"], kw["n_layers"], kw["seq_len"], kw["vocab"]) \
        == ("sdar", 2048, 32, 4, 128, 6, 4096, 18992)
    assert (kw["block_len"], kw["mask_id"], kw["noise_seed"]) \
        == (4, 18991, cell.config["noise_seed"])
    assert (kw["n_experts"], kw["experts_held"], kw["experts_first"],
            kw["experts_per_tok"], kw["expert_width"]) == (128, 8, 0, 8, 768)
    assert (kw["rope_theta"], kw["norm_eps"]) == (1e6, 1e-6)


def test_sdars_mix_keeps_to_the_traffic_its_issue_fixed():
    """ISSUE 51 fixed the mix before any code was written: the rate one
    of three, the budget a whole number of micro-steps of 4096 clean
    tokens, momentum 0.9, two rounds of warm-up, closed loop in one
    process; the four new metrics and the six appended ones are the
    cell's; the tiles and the noise's time move the rate, the noise's
    share and the gap the loss."""
    cell = spec_mod.load_cell(SDAR_CELL)
    mix = cell.traffic
    steps, rest = divmod(mix["token_budget"],
                         mix["batch"] * cell.config["train_seq"])
    assert rest == 0 and steps >= 8
    assert mix["lr"] in (0.003, 0.01, 0.03)
    assert (mix["launcher"]["mom"], mix["warmup_rounds"], mix["su"],
            mix["batch"], mix["launcher"]["np"],
            mix["launcher"]["lm_use_flash"]) == (0.9, 2, 1, 1, 1, 1)
    moves = {m["name"]: m["moves"] for m in cell.metrics("per_layer")}
    assert set(SDAR_METRICS + KEYE_APPENDED) <= set(moves)
    assert {moves[m] for m in SDAR_METRICS[:2]} == {"tokens_per_s"}
    assert {moves[m] for m in SDAR_METRICS[2:]} == {"loss_at_budget"}
    layers = {m["name"]: m["layer"] for m in cell.bench["per_layer"]}
    assert layers["blockdiff_dead_tiles_pct"] == layers["flash_roofline"]
    assert layers["noise_ms_per_step"] == layers["dsa_ms_per_step"]
    for metric in cell.bench["per_layer"]:
        if metric["name"] in SDAR_METRICS:
            assert metric["workloads"] == [SDAR_CELL]
        elif metric["name"] in KEYE_APPENDED:
            # the cell of PR 53 follows them
            assert [c for c in metric["workloads"]
                    if c in (KEYE_CELL, SDAR_CELL)] == [KEYE_CELL, SDAR_CELL]
        elif "workloads" in metric:
            assert SDAR_CELL not in metric["workloads"], metric["name"]
    # added together and in order (PR 53's four follow them, and its
    # configuration and cell)
    assert [m["name"] for m in cell.bench["per_layer"][-26:-22]] == list(
        SDAR_METRICS)
    assert (cell.bench["configs"][-5]["name"],
            cell.bench["workloads"][-5]["name"]) == (cell.config_name,
                                                     SDAR_CELL)


def test_sdars_readers_find_nothing_in_a_run_without_the_block():
    """What the parent's traced run hands them: a cell whose
    configuration lists no such scope, a program that recorded no such
    counter, no device trace, no merged trace: None, no raise."""
    for name in ("keye-l6e8-local", "c111m-local"):
        cell = spec_mod.load_cell(name)
        run = {"cell": cell, "reduction": {"step_module": "jit__lambda"},
               "obs_trace": None, "peaks": None, "results": {},
               "summary": {"worker_ranks": [0], "window": [0.0, 1.0]}}
        for metric in SDAR_METRICS:
            reader = spec_mod.load_reader(cell.root, cell.bench, metric)
            assert reader is not None and reader(dict(run)) is None


def test_sdars_readers_read_a_hand_made_run(monkeypatch):
    """The four readers, the six shared ones and the metrics without a
    ``workloads`` list that the cell has to report, on a scope table and
    a span tree made by hand."""
    from chipbench import flops
    from chipbench.layers import spantree

    cell = spec_mod.load_cell(SDAR_CELL)
    listed = [m["name"] for m in cell.metrics("per_layer")]
    unlisted = [m["name"] for m in cell.bench["per_layer"]
                if "workloads" not in m]
    assert len(unlisted) == 10 and set(unlisted) <= set(listed)
    last = cell.traffic["token_budget"] // 4096 - 1

    class Round:
        def __init__(self, k, whole, alone):
            self.args = {"round": k, "diff_masked_share": [0.625],
                         "diff_nll_c1": [alone], "diff_nll_c2": [3.0],
                         "diff_nll_c3": [3.0], "diff_nll_c4": [whole],
                         "attn_tiles_visited": [7680.0] * 6,
                         "attn_tiles_live": [7680.0] * 5 + [3840.0],
                         "moe_held_rows_share": [0.0625] * 6,
                         "moe_load_max_over_mean": [2.0, 6.5, 2.25, 3.0, 2.0,
                                                    2.0],
                         "moe_compact_share": [1.0] * 5 + [0.0]}

    class Tree:
        def rounds(self):
            # the budget's four steps and one after them, which the gap
            # does not read
            return [Round(last - 3, 4.0, 3.0), Round(last - 2, 4.0, 3.5),
                    Round(last - 1, 4.5, 3.5), Round(last, 4.5, 4.0),
                    Round(last + 1, 9.0, 1.0)]

    table = {"step": 314.0, "noise": 0.02, "attn": 196.0, "router": 10.0,
             "dispatch": 23.0, "experts": 14.0, "head_loss": 7.5,
             "update": 13.0}
    monkeypatch.setattr(spantree, "scope_ms_per_step", lambda run: table)
    monkeypatch.setattr(spantree, "xplane_path", lambda run: None)
    run = {"cell": cell, "peaks": flops.load_peaks("TPU v5 lite"),
           spantree.CACHE_KEY: Tree(),
           "summary": {"tokens_per_s": 13000.0, "worker_ranks": [0]},
           "reduction": {"step_module": "jit__lambda", "step_module_runs": 2,
                         "mosaic_by_scope": {
                             "attn": (36, 0.214), "experts": (72, 0.020),
                             "update": (2, 0.026)}}}

    def read(name):
        return spec_mod.load_reader(cell.root, cell.bench, name)(run)

    # a twelfth of the visited tiles is dead in the hand-made counts
    assert read("blockdiff_dead_tiles_pct") == pytest.approx(100.0 / 12)
    assert read("noise_ms_per_step") == pytest.approx(0.02)
    assert read("diff_masked_pct") == pytest.approx(62.5)
    assert read("diff_nll_gap_nats") == pytest.approx(0.75)
    assert read("dispatch_ms_per_step") == pytest.approx(33.0)
    assert read("held_experts_ms_per_step") == pytest.approx(14.0)
    assert read("held_rows_share_pct") == pytest.approx(6.25)
    assert read("expert_load_max_over_mean") == pytest.approx(6.5)
    assert read("compact_dispatch_pct") == pytest.approx(100.0 * 5 / 6)
    assert read("head_loss_ms_per_step") == pytest.approx(7.5)
    assert read("flash_ms_per_step") == pytest.approx(107.0)
    family = cell.arithmetic().kernels(cell.config, 1)["attn"]
    assert read("flash_roofline") == pytest.approx(
        100 * family["flops"] / 197e12 / 0.107)
    experts = cell.arithmetic().experts_cost(cell.config, 1)
    assert read("held_experts_roofline") == pytest.approx(
        100 * max(experts["flops"] / 197e12, experts["bytes"] / 819e9)
        / 0.010)
    assert read("mfu_pct") == pytest.approx(
        100 * 2_990_211_072 * 13000.0 / 197e12)
    for metric in cell.metrics("per_layer"):
        assert spec_mod.load_reader(cell.root, cell.bench,
                                    metric["name"]) is not None


def sdar_cases():
    return spec_mod.load_cell(SDAR_CELL).arithmetic().hand_worked()


@pytest.mark.parametrize("what,got,want", sdar_cases(),
                         ids=[c[0] for c in sdar_cases()])
def test_sdar_arithmetic_by_hand_through_the_cell(what, got, want):
    assert got == want, what


# -- the Trinity configuration (PR 53) --------------------------------------------

TRINITY_CELL = "trinity-l5e8-local"
TRINITY_METRICS = ("bias_rule_ms_per_step", "attn_gate_ms_per_step",
                   "bias_rule_balance_x", "router_bias_abs_mean")
TRINITY_APPENDED = (
    "dispatch_ms_per_step", "expert_load_max_over_mean",
    "held_experts_ms_per_step", "held_experts_roofline",
    "held_rows_share_pct", "compact_dispatch_pct", "router_bias_flips_pct",
    "shared_expert_ms_per_step", "attn_window_ms_per_step",
    "attn_window_roofline")


def test_trinity_file_has_the_catalogs_keys_and_the_floor_cuts():
    """Every key of the catalog's entry under its own name (the
    model-configs guide's ``architectures.jsonl``, read where it is
    installed; ``tests/test_trinity.py`` holds the hand-copied values);
    only the depth, the dense layers and the experts held, the
    vocabulary and the held layers' kinds differ, with the published
    values beside them; no width is cut; what the catalog does not give
    is stated as assumed."""
    import pathlib

    cell = spec_mod.load_cell(TRINITY_CELL)
    config = cell.config
    path = pathlib.Path(
        "/opt/skills/guides/model-configs/architectures.jsonl")
    if path.exists():
        rows = [json.loads(line) for line in path.read_text().splitlines()]
        entry = next(r for r in rows if r["name"] == "Trinity-Mini")
        catalog = entry["config"]
        assert entry["source_url"] == config["source"]
        assert all(key in config for key in catalog)
        differ = sorted(k for k, v in catalog.items() if config[k] != v)
        assert differ == sorted(config["reduced"])
        assert config["published"] == {k: catalog[k]
                                       for k in config["reduced"]}
        assert config["layer_types"] == catalog["layer_types"][1:6]
    assert sorted(config["reduced"]) == [
        "layer_types", "num_dense_layers", "num_experts",
        "num_hidden_layers", "vocab_size"]
    assert (config["num_hidden_layers"], config["num_dense_layers"],
            config["num_experts"], config["vocab_size"]) == (
                5, 1, 8, 200192 // 8)
    assert (config["hidden_size"], config["num_attention_heads"],
            config["num_key_value_heads"], config["head_dim"],
            config["intermediate_size"], config["moe_intermediate_size"],
            config["num_experts_per_tok"], config["sliding_window"],
            config["rope_theta"], config["route_scale"],
            config["load_balance_coeff"]) == (
                2048, 32, 4, 128, 6144, 1024, 8, 2048, 10000, 2.826, 0.001)
    assert config["router_experts"] == 128   # the router keeps its width
    assert config["train_seq"] == 8192
    entry = next(c for c in cell.bench["configs"]
                 if c["name"] == cell.config_name)
    assert sorted(entry["reduced"]) == sorted(config["reduced"])
    assert entry["source"] == config["source"]
    assert (cell.chips, cell.traffic_name) == (1, "local-msgd-s8k-trinity")
    assert ["embed", "attn", "attn_window", "attn_gate", "router",
            "bias_rule", "dispatch", "experts", "shared_expert",
            "dense_mlp", "head_loss", "update"] == config["scopes"]
    assert all(key in config for key in config["launcher_from"].values())
    assert all(key in config for key in config["tiny"])
    assert len(config["assumed"]) >= 8 and "16 v5e chips" in \
        config["deployment"]
    worked = [got for _what, got, want in cell.arithmetic().hand_worked()
              if got == want]
    assert all(count in worked for count in (
        504_147_712, 65_020_160, 84_156_800))
    assert cell.arithmetic().param_count(config) == 504_147_712
    assert cell.reference().LOSS_TOL_NATS > 0 < cell.reference().GRAD_REL_TOL


def test_the_launcher_builds_the_balanced_block_from_the_cells_files():
    from chipbench import run as runner
    from mpit_tpu.lm.model import build_kw
    from mpit_tpu.train.launch import lm_trainer_cfg

    cell = spec_mod.load_cell(TRINITY_CELL)
    kw = build_kw(lm_trainer_cfg(runner.launch_config(cell, seed=5)))
    assert (kw["arch"], kw["d_model"], kw["n_heads"], kw["kv_heads"],
            kw["head_dim"], kw["n_layers"], kw["seq_len"], kw["vocab"]) \
        == ("trinity", 2048, 32, 4, 128, 5, 8192, 25024)
    assert kw["layer_types"].split(",") == cell.config["layer_types"]
    assert (kw["window"], kw["dense_layers"], kw["dense_width"]) == (
        2048, 1, 6144)
    assert (kw["n_experts"], kw["experts_held"], kw["experts_first"],
            kw["experts_per_tok"], kw["expert_width"],
            kw["shared_experts"]) == (128, 8, 0, 8, 1024, 1)
    assert (kw["route_scale"], kw["bias_rate"], kw["rope_theta"],
            kw["norm_eps"]) == (2.826, 0.001, 1e4, 1e-5)
    assert kw["embed_scale"] == pytest.approx(2048 ** 0.5)


def test_trinitys_mix_keeps_to_the_traffic_its_issue_fixed():
    """ISSUE 53 fixed the mix before any code was written: the rate one
    of three, the budget a whole number of micro-steps of 8192 tokens,
    momentum 0.9, two rounds of warm-up, closed loop in one process; the
    four new metrics and the ten appended ones are the cell's; the
    rule's time, the gate's and what the rule buys move the rate, the
    bias's size the loss."""
    cell = spec_mod.load_cell(TRINITY_CELL)
    mix = cell.traffic
    steps, rest = divmod(mix["token_budget"],
                         mix["batch"] * cell.config["train_seq"])
    assert rest == 0 and steps >= 8
    assert mix["lr"] in (0.003, 0.01, 0.03)
    assert (mix["launcher"]["mom"], mix["warmup_rounds"], mix["su"],
            mix["batch"], mix["launcher"]["np"],
            mix["launcher"]["lm_use_flash"]) == (0.9, 2, 1, 1, 1, 1)
    assert "lm_bias_rate" not in mix["launcher"]   # the configuration's
    moves = {m["name"]: m["moves"] for m in cell.metrics("per_layer")}
    assert set(TRINITY_METRICS + TRINITY_APPENDED) <= set(moves)
    assert {moves[m] for m in TRINITY_METRICS[:3]} == {"tokens_per_s"}
    assert moves["router_bias_abs_mean"] == "loss_at_budget"
    layers = {m["name"]: m["layer"] for m in cell.bench["per_layer"]}
    assert {layers[m] for m in TRINITY_METRICS} == {
        layers["dispatch_ms_per_step"]}
    for metric in cell.bench["per_layer"]:
        if metric["name"] == "attn_gate_ms_per_step":
            # PR 61's cell has a gated attention too
            assert metric["workloads"] == [TRINITY_CELL, QWEN3NEXT_CELL]
        elif metric["name"] in TRINITY_METRICS:
            assert metric["workloads"] == [TRINITY_CELL]
        elif metric["name"] in TRINITY_APPENDED:
            assert TRINITY_CELL in metric["workloads"][-3:]
        elif "workloads" in metric:
            assert TRINITY_CELL not in metric["workloads"], metric["name"]
    # added together, in order and last
    assert [m["name"] for m in cell.bench["per_layer"][-22:-18]] == list(
        TRINITY_METRICS)
    assert (cell.bench["configs"][-4]["name"],
            cell.bench["workloads"][-4]["name"]) == (cell.config_name,
                                                     TRINITY_CELL)
    assert cell.chips == 1 and len(cell.why) <= 200


def test_trinitys_readers_find_nothing_in_a_run_without_the_block():
    """What the parent's traced run hands them: a cell whose
    configuration lists no such scope, a program that recorded no such
    counter, no device trace, no merged trace: None, no raise."""
    for name in ("joyai-l5e8-local", "c111m-local"):
        cell = spec_mod.load_cell(name)
        run = {"cell": cell, "reduction": {"step_module": "jit__lambda"},
               "obs_trace": None, "peaks": None, "results": {},
               "summary": {"worker_ranks": [0], "window": [0.0, 1.0]}}
        for metric in TRINITY_METRICS:
            reader = spec_mod.load_reader(cell.root, cell.bench, metric)
            assert reader is not None and reader(dict(run)) is None


def test_trinitys_readers_read_a_hand_made_run(monkeypatch):
    """The four readers, the ten shared ones and the metrics without a
    ``workloads`` list that the cell has to report, on a scope table and
    a span tree made by hand; and a block with a bias and no rule
    (JoyAI's rounds carry no ``moe_bias_abs_mean``) reads nothing of the
    rule's."""
    from chipbench import flops
    from chipbench.layers import spantree

    cell = spec_mod.load_cell(TRINITY_CELL)
    listed = [m["name"] for m in cell.metrics("per_layer")]
    unlisted = [m["name"] for m in cell.bench["per_layer"]
                if "workloads" not in m]
    assert len(unlisted) == 10 and set(unlisted) <= set(listed)

    class Round:
        def __init__(self, k, load, size, rule=True):
            self.args = {"round": k,
                         "moe_held_rows_share": [0.0625] * 4,
                         "moe_load_max_over_mean": [2.0, load, 2.25, 2.0],
                         "moe_compact_share": [1.0] * 4,
                         "moe_bias_flips_share": [0.1, 0.2, 0.3, 0.2]}
            if rule:
                self.args.update(
                    moe_bias_abs_mean=[size] * 4,
                    moe_bias_step_nonzero_share=[1.0] * 4)

    class Tree:
        def __init__(self, rule=True):
            self.rule = rule

        def rounds(self):
            # four uneven rounds, two between, four more even ones
            loads = [6.0] * 4 + [5.0, 4.0] + [3.0] * 4
            return [Round(k, load, 0.016 + 0.001 * k, self.rule)
                    for k, load in enumerate(loads)]

    table = {"step": 300.0, "attn": 40.0, "attn_window": 60.0,
             "attn_gate": 9.0, "router": 8.0, "bias_rule": 0.25,
             "dispatch": 20.0, "experts": 30.0, "shared_expert": 12.0,
             "dense_mlp": 14.0, "head_loss": 11.0, "update": 13.0}
    monkeypatch.setattr(spantree, "scope_ms_per_step", lambda run: table)
    monkeypatch.setattr(spantree, "xplane_path", lambda run: None)
    run = {"cell": cell, "peaks": flops.load_peaks("TPU v5 lite"),
           spantree.CACHE_KEY: Tree(),
           "summary": {"tokens_per_s": 27000.0, "worker_ranks": [0]},
           "reduction": {"step_module": "jit__lambda", "step_module_runs": 2,
                         "mosaic_by_scope": {
                             "attn": (6, 0.070), "attn_window": (24, 0.100),
                             "experts": (48, 0.040), "update": (2, 0.026)}}}

    def read(name, run=run):
        return spec_mod.load_reader(cell.root, cell.bench, name)(run)

    assert read("bias_rule_ms_per_step") == pytest.approx(0.25)
    assert read("attn_gate_ms_per_step") == pytest.approx(9.0)
    assert read("bias_rule_balance_x") == pytest.approx(2.0)
    assert read("router_bias_abs_mean") == pytest.approx(0.025)
    assert read("dispatch_ms_per_step") == pytest.approx(28.0)
    assert read("held_experts_ms_per_step") == pytest.approx(30.0)
    assert read("shared_expert_ms_per_step") == pytest.approx(12.0)
    assert read("held_rows_share_pct") == pytest.approx(6.25)
    assert read("expert_load_max_over_mean") == pytest.approx(4.5)
    assert read("compact_dispatch_pct") == pytest.approx(100.0)
    assert read("router_bias_flips_pct") == pytest.approx(20.0)
    assert read("head_loss_ms_per_step") == pytest.approx(11.0)
    assert read("flash_ms_per_step") == pytest.approx(35.0)
    assert read("attn_window_ms_per_step") == pytest.approx(50.0)
    families = cell.arithmetic().kernels(cell.config, 1)
    assert read("flash_roofline") == pytest.approx(
        100 * families["attn"]["flops"] / 197e12 / 0.035)
    assert read("attn_window_roofline") == pytest.approx(
        100 * families["attn_window"]["flops"] / 197e12 / 0.050)
    experts = cell.arithmetic().experts_cost(cell.config, 1)
    assert read("held_experts_roofline") == pytest.approx(
        100 * max(experts["flops"] / 197e12, experts["bytes"] / 819e9)
        / 0.020)
    assert read("mfu_pct") == pytest.approx(
        100 * 2_138_357_760 * 27000.0 / 197e12)
    for metric in cell.metrics("per_layer"):
        assert spec_mod.load_reader(cell.root, cell.bench,
                                    metric["name"]) is not None
    # a block whose bias nothing moves: the rule's two read nothing
    still = {**run, spantree.CACHE_KEY: Tree(rule=False)}
    assert read("bias_rule_balance_x", still) is None
    assert read("router_bias_abs_mean", still) is None
    assert read("router_bias_flips_pct", still) == pytest.approx(20.0)


def trinity_cases():
    return spec_mod.load_cell(TRINITY_CELL).arithmetic().hand_worked()


@pytest.mark.parametrize("what,got,want", trinity_cases(),
                         ids=[c[0] for c in trinity_cases()])
def test_trinity_arithmetic_by_hand_through_the_cell(what, got, want):
    assert got == want, what


# -- the Nemotron-3-Nano configuration (PR 58) ------------------------------------

NEMOTRON_METRICS = ("ssm_ms_per_step", "ssd_scan_ms_per_step",
                    "ssd_scan_roofline", "ssm_conv_ms_per_step",
                    "ssm_decay_mean")
NEMOTRON_APPENDED = (
    "dispatch_ms_per_step", "expert_load_max_over_mean",
    "held_experts_ms_per_step", "held_experts_roofline",
    "held_rows_share_pct", "compact_dispatch_pct",
    "shared_expert_ms_per_step", "router_bias_flips_pct")


def test_nemotron_file_has_the_catalogs_keys_and_the_floor_cuts():
    """Every key of the catalog's row under its own name and at its
    published value but the four cut: the depth, the pattern cut to it,
    the experts held and the vocabulary, with the published values
    beside them; no width is cut; what the row does not give is stated
    as assumed."""
    import pathlib

    cell = spec_mod.load_cell(NEMOTRON_CELL)
    config = cell.config
    path = pathlib.Path(
        "/opt/skills/guides/model-configs/architectures.jsonl")
    if path.exists():
        rows = [json.loads(line) for line in path.read_text().splitlines()]
        entry = next(r for r in rows
                     if r["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16")
        catalog = entry["config"]
        assert entry["source_url"] == config["source"]
        assert all(key in config for key in catalog)
        differ = sorted(k for k, v in catalog.items() if config[k] != v)
        assert differ == sorted(config["reduced"])
        assert config["published"] == {k: catalog[k]
                                       for k in config["reduced"]}
        assert catalog["hybrid_override_pattern"].startswith(
            config["hybrid_override_pattern"])
    assert sorted(config["reduced"]) == [
        "hybrid_override_pattern", "n_routed_experts", "num_hidden_layers",
        "vocab_size"]
    # the floors: a whole period of nine, 8 experts, an eighth of the rows
    assert (config["num_hidden_layers"], config["hybrid_override_pattern"],
            config["n_routed_experts"], config["vocab_size"]) == (
                9, "MEMEM*EME", 8, 131072 // 8)
    assert (config["hidden_size"], config["num_attention_heads"],
            config["num_key_value_heads"], config["head_dim"],
            config["mamba_num_heads"], config["mamba_head_dim"],
            config["n_groups"], config["ssm_state_size"],
            config["conv_kernel"], config["chunk_size"],
            config["moe_intermediate_size"],
            config["moe_shared_expert_intermediate_size"],
            config["num_experts_per_tok"], config["routed_scaling_factor"],
            config["norm_eps"], config["layer_norm_epsilon"]) == (
                2688, 32, 2, 128, 64, 64, 8, 128, 4, 128, 1856, 3712, 6,
                2.5, 1e-5, 1e-5)
    assert config["router_experts"] == 128   # the router keeps its width
    assert config["num_experts"] == config["n_routed_experts"]
    assert (config["train_seq"], config["experts_first"],
            config["rescale_depth"]) == (8192, 0, 52)
    entry = next(c for c in cell.bench["configs"]
                 if c["name"] == cell.config_name)
    assert sorted(entry["reduced"]) == sorted(config["reduced"])
    assert entry["source"] == config["source"]
    assert (cell.chips, cell.traffic_name) == (
        1, "local-msgd-s8k-nemotron3")
    assert ["embed", "ssm_proj", "ssm_conv", "ssd_scan", "ssm_norm", "attn",
            "router", "dispatch", "experts", "shared_expert", "head_loss",
            "update"] == config["scopes"]
    assert all(key in config for key in config["launcher_from"].values())
    assert all(key in config for key in config["tiny"])
    assert len(config["assumed"]) >= 8 and "16 v5e chips" in \
        config["deployment"]
    worked = [got for _what, got, want in cell.arithmetic().hand_worked()
              if got == want]
    # a Mamba layer, an attention layer, a sparse layer, the vector
    assert all(count in worked for count in (
        38_744_896, 23_399_040, 100_125_440, 666_963_456))
    assert cell.arithmetic().param_count(config) == 666_963_456
    assert cell.reference().LOSS_TOL_NATS > 0 < cell.reference().GRAD_REL_TOL


def test_the_launcher_builds_the_state_space_block_from_the_cells_files():
    from chipbench import run as runner
    from mpit_tpu.lm.model import build_kw
    from mpit_tpu.train.launch import lm_trainer_cfg

    cell = spec_mod.load_cell(NEMOTRON_CELL)
    kw = build_kw(lm_trainer_cfg(runner.launch_config(cell, seed=5)))
    assert (kw["arch"], kw["d_model"], kw["n_heads"], kw["kv_heads"],
            kw["head_dim"], kw["n_layers"], kw["seq_len"], kw["vocab"]) \
        == ("nemotron", 2688, 32, 2, 128, 9, 8192, 16384)
    assert kw["layer_types"] == cell.arithmetic().layer_types(cell.config)
    assert kw["layer_types"].split(",").count("mamba") == 4
    assert (kw["ssm_heads"], kw["ssm_head_dim"], kw["ssm_groups"],
            kw["ssm_state"], kw["ssm_chunk"], kw["conv_kernel"]) == (
                64, 64, 8, 128, 128, 4)
    assert (kw["n_experts"], kw["experts_held"], kw["experts_first"],
            kw["experts_per_tok"], kw["expert_width"], kw["shared_experts"],
            kw["shared_width"]) == (128, 8, 0, 6, 1856, 1, 3712)
    assert (kw["route_scale"], kw["norm_eps"], kw["init_depth"]) == (
        2.5, 1e-5, 52)


def test_nemotrons_mix_keeps_to_the_traffic_its_issue_fixed():
    """ISSUE 58 fixed the mix before any code was written: the rate one
    of four, the budget a whole number of micro-steps of 8192 tokens
    between a tenth and four fifths of a window's, momentum 0.9, two
    rounds of warm-up, closed loop in one process; the five new metrics
    and the eight appended ones are the cell's, every one moving the
    rate, and the budget's table is in the mix's own file."""
    cell = spec_mod.load_cell(NEMOTRON_CELL)
    mix = cell.traffic
    steps, rest = divmod(mix["token_budget"],
                         mix["batch"] * cell.config["train_seq"])
    assert rest == 0 and steps >= 8
    assert mix["lr"] in (0.003, 0.01, 0.03, 0.1)
    assert (mix["launcher"]["mom"], mix["warmup_rounds"], mix["su"],
            mix["batch"], mix["launcher"]["np"],
            mix["launcher"]["lm_use_flash"]) == (0.9, 2, 1, 1, 1, 1)
    assert "quartile distance" in mix["chosen_because"]
    moves = {m["name"]: m["moves"] for m in cell.metrics("per_layer")}
    assert set(NEMOTRON_METRICS + NEMOTRON_APPENDED) <= set(moves)
    assert {moves[m] for m in NEMOTRON_METRICS} == {"tokens_per_s"}
    layers = {m["name"]: m["layer"] for m in cell.bench["per_layer"]}
    assert layers["ssd_scan_roofline"] == layers["kda_scan_roofline"]
    assert layers["ssm_ms_per_step"] == layers["kda_ms_per_step"]
    units = {m["name"]: (m["unit"], m["better"])
             for m in cell.bench["per_layer"]}
    assert units["ssd_scan_roofline"] == ("%", "higher")
    assert units["ssm_decay_mean"] == ("share", "lower")
    for metric in cell.bench["per_layer"]:
        if metric["name"] in NEMOTRON_METRICS:
            # PR 65's cell, the second with state-space layers, follows
            assert metric["workloads"] == [NEMOTRON_CELL, GRANITE_CELL]
        elif metric["name"] in NEMOTRON_APPENDED:
            assert NEMOTRON_CELL in metric["workloads"][-2:]
        elif "workloads" in metric:
            assert NEMOTRON_CELL not in metric["workloads"], metric["name"]
    # added together and in order (PR 61's six and PR 65's three
    # follow them, and their configurations and cells)
    assert [m["name"] for m in cell.bench["per_layer"][-18:-13]] == list(
        NEMOTRON_METRICS)
    assert (cell.bench["configs"][-3]["name"],
            cell.bench["workloads"][-3]["name"]) == (cell.config_name,
                                                     NEMOTRON_CELL)
    assert cell.chips == 1 and len(cell.why) <= 200


def test_nemotrons_readers_find_nothing_in_a_run_without_the_block():
    """What the parent's traced run hands them: a cell whose
    configuration lists no such scope, a program that recorded no such
    counter, no device trace, no merged trace: None, no raise."""
    for name in ("kimi-linear-l5e8-local", "c111m-local"):
        cell = spec_mod.load_cell(name)
        run = {"cell": cell, "reduction": {"step_module": "jit__lambda"},
               "obs_trace": None, "peaks": None, "results": {},
               "summary": {"worker_ranks": [0], "window": [0.0, 1.0]}}
        for metric in NEMOTRON_METRICS:
            reader = spec_mod.load_reader(cell.root, cell.bench, metric)
            assert reader is not None and reader(dict(run)) is None


def test_nemotrons_readers_read_a_hand_made_run(monkeypatch):
    """The five readers, the eight shared ones and the metrics without a
    ``workloads`` list that the cell has to report, on a scope table and
    a span tree made by hand."""
    from chipbench import flops
    from chipbench.layers import spantree

    cell = spec_mod.load_cell(NEMOTRON_CELL)
    listed = [m["name"] for m in cell.metrics("per_layer")]
    unlisted = [m["name"] for m in cell.bench["per_layer"]
                if "workloads" not in m]
    assert len(unlisted) == 10 and set(unlisted) <= set(listed)

    class Round:
        def __init__(self, k, decay):
            self.args = {"round": k,
                         "lm_ssm_decay_mean": [decay, decay + 0.02,
                                               decay - 0.02, decay],
                         "moe_held_rows_share": [0.0625] * 4,
                         "moe_load_max_over_mean": [2.0, 3.0, 2.25, 2.0],
                         "moe_compact_share": [1.0] * 4,
                         "moe_bias_flips_share": [0.1, 0.2, 0.3, 0.2]}

    class Tree:
        def rounds(self):
            return [Round(k, 0.88 + 0.01 * k) for k in range(5)]

    table = {"step": 400.0, "ssm_proj": 60.0, "ssm_conv": 12.0,
             "ssd_scan": 50.0, "ssm_norm": 6.0, "attn": 40.0,
             "router": 8.0, "dispatch": 20.0, "experts": 30.0,
             "shared_expert": 24.0, "head_loss": 11.0, "update": 13.0}
    monkeypatch.setattr(spantree, "scope_ms_per_step", lambda run: table)
    monkeypatch.setattr(spantree, "xplane_path", lambda run: None)
    run = {"cell": cell, "peaks": flops.load_peaks("TPU v5 lite"),
           spantree.CACHE_KEY: Tree(),
           "summary": {"tokens_per_s": 20000.0, "worker_ranks": [0]},
           "reduction": {"step_module": "jit__lambda", "step_module_runs": 2,
                         "mosaic_by_scope": {
                             "attn": (6, 0.070), "experts": (48, 0.040),
                             "update": (2, 0.026)}}}

    def read(name, run=run):
        return spec_mod.load_reader(cell.root, cell.bench, name)(run)

    assert read("ssm_ms_per_step") == pytest.approx(128.0)
    assert read("ssd_scan_ms_per_step") == pytest.approx(50.0)
    assert read("ssm_conv_ms_per_step") == pytest.approx(12.0)
    assert read("ssm_decay_mean") == pytest.approx(0.90)
    scan = cell.arithmetic().ssd_scan_cost(cell.config, 1)
    assert read("ssd_scan_roofline") == pytest.approx(
        100 * max(scan["flops"] / 197e12, scan["bytes"] / 819e9) / 0.050)
    assert scan["bytes"] / 819e9 > scan["flops"] / 197e12   # memory binds
    assert read("dispatch_ms_per_step") == pytest.approx(28.0)
    assert read("held_experts_ms_per_step") == pytest.approx(30.0)
    assert read("shared_expert_ms_per_step") == pytest.approx(24.0)
    assert read("held_rows_share_pct") == pytest.approx(6.25)
    assert read("expert_load_max_over_mean") == pytest.approx(3.0)
    assert read("compact_dispatch_pct") == pytest.approx(100.0)
    assert read("router_bias_flips_pct") == pytest.approx(20.0)
    assert read("head_loss_ms_per_step") == pytest.approx(11.0)
    assert read("flash_ms_per_step") == pytest.approx(35.0)
    families = cell.arithmetic().kernels(cell.config, 1)
    assert read("flash_roofline") == pytest.approx(
        100 * families["attn"]["flops"] / 197e12 / 0.035)
    experts = cell.arithmetic().experts_cost(cell.config, 1)
    assert read("held_experts_roofline") == pytest.approx(
        100 * max(experts["flops"] / 197e12, experts["bytes"] / 819e9)
        / 0.020)
    assert read("mfu_pct") == pytest.approx(
        100 * 2_144_968_704 * 20000.0 / 197e12)
    for metric in cell.metrics("per_layer"):
        assert spec_mod.load_reader(cell.root, cell.bench,
                                    metric["name"]) is not None
    # a block with no state-space layer carries no decay: nothing read
    class Bare:
        def rounds(self):
            rounds = Tree().rounds()
            for r in rounds:
                del r.args["lm_ssm_decay_mean"]
            return rounds

    assert read("ssm_decay_mean", {**run, spantree.CACHE_KEY: Bare()}) \
        is None


def nemotron_cases():
    return spec_mod.load_cell(NEMOTRON_CELL).arithmetic().hand_worked()


@pytest.mark.parametrize("what,got,want", nemotron_cases(),
                         ids=[c[0] for c in nemotron_cases()])
def test_nemotron_arithmetic_by_hand_through_the_cell(what, got, want):
    assert got == want, what


# -- the Qwen3-Next configuration (PR 61) -----------------------------------------

QWEN3NEXT_METRICS = ("gdn_ms_per_step", "gdn_scan_ms_per_step",
                     "gdn_scan_roofline", "gdn_conv_ms_per_step",
                     "gdn_decay_mean", "shared_gate_mean")
QWEN3NEXT_APPENDED = (
    "dispatch_ms_per_step", "expert_load_max_over_mean",
    "held_experts_ms_per_step", "held_experts_roofline",
    "held_rows_share_pct", "compact_dispatch_pct",
    "shared_expert_ms_per_step", "attn_gate_ms_per_step")


def test_qwen3next_file_has_the_catalogs_keys_and_the_floor_cuts():
    """Every key of the catalog's row under its own name and at its
    published value but the three cut: the depth, the experts held and
    the vocabulary, with the published values beside them; no width is
    cut; what the row does not give is stated as assumed."""
    import pathlib

    cell = spec_mod.load_cell(QWEN3NEXT_CELL)
    config = cell.config
    path = pathlib.Path(
        "/opt/skills/guides/model-configs/architectures.jsonl")
    if path.exists():
        rows = [json.loads(line) for line in path.read_text().splitlines()]
        entry = next(r for r in rows
                     if r["name"] == "Qwen3-Next-80B-A3B-Instruct")
        catalog = entry["config"]
        assert entry["source_url"] == config["source"]
        assert all(key in config for key in catalog)
        differ = sorted(k for k, v in catalog.items() if config[k] != v)
        assert differ == sorted(config["reduced"])
        assert config["published"] == {k: catalog[k]
                                       for k in config["reduced"]}
    assert sorted(config["reduced"]) == ["num_experts", "num_hidden_layers",
                                         "vocab_size"]
    # the floors: one whole period of four, 32 experts, an eighth of the rows
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"], config["full_attention_interval"]) == (
                4, 32, 151936 // 8, 4)
    assert (config["hidden_size"], config["num_attention_heads"],
            config["num_key_value_heads"], config["head_dim"],
            config["linear_num_key_heads"], config["linear_num_value_heads"],
            config["linear_key_head_dim"], config["linear_value_head_dim"],
            config["linear_conv_kernel_dim"], config["moe_intermediate_size"],
            config["shared_expert_intermediate_size"],
            config["num_experts_per_tok"], config["partial_rotary_factor"],
            config["rope_theta"], config["rms_norm_eps"],
            config["norm_topk_prob"]) == (
                2048, 16, 2, 256, 16, 32, 128, 128, 4, 512, 512, 10, 0.25,
                10_000_000, 1e-6, True)
    assert config["router_experts"] == 512   # the router keeps its width
    assert (config["train_seq"], config["experts_first"],
            config["gdn_chunk"]) == (8192, 0, 64)
    entry = next(c for c in cell.bench["configs"]
                 if c["name"] == cell.config_name)
    assert sorted(entry["reduced"]) == sorted(config["reduced"])
    assert entry["source"] == config["source"]
    assert (cell.chips, cell.traffic_name) == (
        1, "local-msgd-s8k-qwen3next")
    assert ["embed", "gdn_proj", "gdn_conv", "gdn_scan", "gdn_out", "attn",
            "attn_gate", "router", "dispatch", "experts", "shared_expert",
            "head_loss", "update"] == config["scopes"]
    assert all(key in config for key in config["launcher_from"].values())
    assert all(key in config for key in config["tiny"])
    assert len(config["assumed"]) >= 8 and "16 v5e chips" in \
        config["deployment"]
    worked = [got for _what, got, want in cell.arithmetic().hand_worked()
              if got == want]
    # a delta mixer, the attention, a sparse MLP, the vector
    assert all(count in worked for count in (
        33_720_512, 27_265_536, 104_861_696, 625_667_136))
    assert cell.arithmetic().param_count(config) == 625_667_136
    assert cell.reference().LOSS_TOL_NATS > 0 < cell.reference().GRAD_REL_TOL


def test_the_launcher_builds_the_gated_delta_block_from_the_cells_files():
    from chipbench import run as runner
    from mpit_tpu.lm.model import build_kw
    from mpit_tpu.train.launch import lm_trainer_cfg

    cell = spec_mod.load_cell(QWEN3NEXT_CELL)
    kw = build_kw(lm_trainer_cfg(runner.launch_config(cell, seed=5)))
    assert (kw["arch"], kw["d_model"], kw["n_heads"], kw["kv_heads"],
            kw["head_dim"], kw["n_layers"], kw["seq_len"], kw["vocab"]) \
        == ("qwen3next", 2048, 16, 2, 256, 4, 8192, 18992)
    assert kw["layer_types"] == cell.arithmetic().layer_types(cell.config)
    assert kw["layer_types"].split(",") == ["linear_attention"] * 3 + [
        "full_attention"]
    assert (kw["gdn_key_heads"], kw["gdn_value_heads"], kw["gdn_key_dim"],
            kw["gdn_value_dim"], kw["conv_kernel"], kw["rotary_factor"]) == (
                16, 32, 128, 128, 4, 0.25)
    assert (kw["n_experts"], kw["experts_held"], kw["experts_first"],
            kw["experts_per_tok"], kw["expert_width"],
            kw["shared_width"]) == (512, 32, 0, 10, 512, 512)
    assert (kw["rope_theta"], kw["norm_eps"]) == (1e7, 1e-6)


def test_qwen3nexts_mix_keeps_to_the_traffic_its_issue_fixed():
    """ISSUE 61 fixed the mix before any code was written: the rate one
    of four, the budget a whole number of micro-steps of 8192 tokens,
    momentum 0.9, two rounds of warm-up, closed loop in one process; the
    six new metrics and the eight appended ones are the cell's, and the
    budget's table is in the mix's own file."""
    cell = spec_mod.load_cell(QWEN3NEXT_CELL)
    mix = cell.traffic
    steps, rest = divmod(mix["token_budget"],
                         mix["batch"] * cell.config["train_seq"])
    assert rest == 0 and steps >= 8
    assert mix["lr"] in (0.003, 0.01, 0.03, 0.1)
    assert (mix["launcher"]["mom"], mix["warmup_rounds"], mix["su"],
            mix["batch"], mix["launcher"]["np"],
            mix["launcher"]["lm_use_flash"]) == (0.9, 2, 1, 1, 1, 1)
    assert "quartile distance" in mix["chosen_because"]
    moves = {m["name"]: m["moves"] for m in cell.metrics("per_layer")}
    assert set(QWEN3NEXT_METRICS + QWEN3NEXT_APPENDED) <= set(moves)
    assert {moves[m] for m in QWEN3NEXT_METRICS[:4]} == {"tokens_per_s"}
    assert {moves[m] for m in QWEN3NEXT_METRICS[4:]} == {"loss_at_budget"}
    layers = {m["name"]: m["layer"] for m in cell.bench["per_layer"]}
    assert layers["gdn_scan_roofline"] == layers["kda_scan_roofline"]
    assert layers["gdn_ms_per_step"] == layers["kda_ms_per_step"]
    units = {m["name"]: (m["unit"], m["better"])
             for m in cell.bench["per_layer"]}
    assert units["gdn_scan_roofline"] == ("%", "higher")
    assert units["gdn_decay_mean"] == units["shared_gate_mean"] == (
        "share", "lower")
    for metric in cell.bench["per_layer"]:
        if metric["name"] in QWEN3NEXT_METRICS:
            assert metric["workloads"] == [QWEN3NEXT_CELL]
        elif metric["name"] in QWEN3NEXT_APPENDED:
            assert metric["workloads"][-1] == QWEN3NEXT_CELL
        elif "workloads" in metric:
            assert QWEN3NEXT_CELL not in metric["workloads"], metric["name"]
    # added together and in order (PR 65's three follow them, and its
    # configuration and cell)
    assert [m["name"] for m in cell.bench["per_layer"][-13:-7]] == list(
        QWEN3NEXT_METRICS)
    assert (cell.bench["configs"][-2]["name"],
            cell.bench["workloads"][-2]["name"]) == (cell.config_name,
                                                     QWEN3NEXT_CELL)
    assert cell.chips == 1 and len(cell.why) <= 200
    assert len(cell.bench["configs"]) == 14 and len(
        cell.bench["workloads"]) == 15


def test_qwen3nexts_readers_find_nothing_in_a_run_without_the_block():
    """What the parent's traced run hands them: a cell whose
    configuration lists no such scope, a program that recorded no such
    counter, no device trace, no merged trace: None, no raise."""
    for name in ("kimi-linear-l5e8-local", "c111m-local"):
        cell = spec_mod.load_cell(name)
        run = {"cell": cell, "reduction": {"step_module": "jit__lambda"},
               "obs_trace": None, "peaks": None, "results": {},
               "summary": {"worker_ranks": [0], "window": [0.0, 1.0]}}
        for metric in QWEN3NEXT_METRICS:
            reader = spec_mod.load_reader(cell.root, cell.bench, metric)
            assert reader is not None and reader(dict(run)) is None


def test_qwen3nexts_readers_read_a_hand_made_run(monkeypatch):
    """The six readers, the eight shared ones and the metrics without a
    ``workloads`` list that the cell has to report, on a scope table and
    a span tree made by hand."""
    from chipbench import flops
    from chipbench.layers import spantree

    cell = spec_mod.load_cell(QWEN3NEXT_CELL)
    listed = [m["name"] for m in cell.metrics("per_layer")]
    unlisted = [m["name"] for m in cell.bench["per_layer"]
                if "workloads" not in m]
    assert len(unlisted) == 10 and set(unlisted) <= set(listed)

    class Round:
        def __init__(self, k, decay):
            self.args = {"round": k,
                         "lm_gdn_decay_mean": [decay, decay + 0.03,
                                               decay - 0.03],
                         "lm_shared_gate_mean": [0.5, 0.52, 0.48, 0.5],
                         "moe_held_rows_share": [0.0625] * 4,
                         "moe_load_max_over_mean": [2.0, 3.0, 2.25, 2.0],
                         "moe_compact_share": [1.0] * 4}

    class Tree:
        def rounds(self):
            return [Round(k, 0.83 + 0.01 * k) for k in range(5)]

    table = {"step": 300.0, "gdn_proj": 50.0, "gdn_conv": 15.0,
             "gdn_scan": 45.0, "gdn_out": 20.0, "attn": 40.0,
             "attn_gate": 3.0, "router": 8.0, "dispatch": 20.0,
             "experts": 30.0, "shared_expert": 9.0, "head_loss": 11.0,
             "update": 60.0}
    monkeypatch.setattr(spantree, "scope_ms_per_step", lambda run: table)
    monkeypatch.setattr(spantree, "xplane_path", lambda run: None)
    run = {"cell": cell, "peaks": flops.load_peaks("TPU v5 lite"),
           spantree.CACHE_KEY: Tree(),
           "summary": {"tokens_per_s": 27000.0, "worker_ranks": [0]},
           "reduction": {"step_module": "jit__lambda", "step_module_runs": 2,
                         "mosaic_by_scope": {
                             "attn": (6, 0.070), "experts": (48, 0.040),
                             "gdn_scan": (18, 0.080), "update": (2, 0.026)}}}

    def read(name, run=run):
        return spec_mod.load_reader(cell.root, cell.bench, name)(run)

    assert read("gdn_ms_per_step") == pytest.approx(130.0)
    assert read("gdn_scan_ms_per_step") == pytest.approx(45.0)
    assert read("gdn_conv_ms_per_step") == pytest.approx(15.0)
    assert read("gdn_decay_mean") == pytest.approx(0.85)
    assert read("shared_gate_mean") == pytest.approx(0.5)
    scan = cell.arithmetic().gdn_scan_cost(cell.config, 1)
    assert read("gdn_scan_roofline") == pytest.approx(
        100 * max(scan["flops"] / 197e12, scan["bytes"] / 819e9) / 0.045)
    # the algorithm's bytes: q and k at 16 heads, the decay a float a head
    assert scan["bytes"] == 3 * (6 * 67_108_864 + 5 * 134_217_728
                                 + 6 * 1_048_576)
    assert read("dispatch_ms_per_step") == pytest.approx(28.0)
    assert read("held_experts_ms_per_step") == pytest.approx(30.0)
    assert read("shared_expert_ms_per_step") == pytest.approx(9.0)
    assert read("attn_gate_ms_per_step") == pytest.approx(3.0)
    assert read("held_rows_share_pct") == pytest.approx(6.25)
    assert read("expert_load_max_over_mean") == pytest.approx(3.0)
    assert read("compact_dispatch_pct") == pytest.approx(100.0)
    assert read("head_loss_ms_per_step") == pytest.approx(11.0)
    assert read("flash_ms_per_step") == pytest.approx(35.0)
    families = cell.arithmetic().kernels(cell.config, 1)
    assert read("flash_roofline") == pytest.approx(
        100 * families["attn"]["flops"] / 197e12 / 0.035)
    experts = cell.arithmetic().experts_cost(cell.config, 1)
    assert read("held_experts_roofline") == pytest.approx(
        100 * max(experts["flops"] / 197e12, experts["bytes"] / 819e9)
        / 0.020)
    assert read("mfu_pct") == pytest.approx(
        100 * 1_390_288_896 * 27000.0 / 197e12)
    for metric in cell.metrics("per_layer"):
        assert spec_mod.load_reader(cell.root, cell.bench,
                                    metric["name"]) is not None

    # a block with no delta layer and no gated shared expert: nothing read
    class Bare:
        def rounds(self):
            rounds = Tree().rounds()
            for r in rounds:
                del r.args["lm_gdn_decay_mean"]
                del r.args["lm_shared_gate_mean"]
            return rounds

    bare = {**run, spantree.CACHE_KEY: Bare()}
    assert read("gdn_decay_mean", bare) is None
    assert read("shared_gate_mean", bare) is None


def qwen3next_cases():
    return spec_mod.load_cell(QWEN3NEXT_CELL).arithmetic().hand_worked()


@pytest.mark.parametrize("what,got,want", qwen3next_cases(),
                         ids=[c[0] for c in qwen3next_cases()])
def test_qwen3next_arithmetic_by_hand_through_the_cell(what, got, want):
    assert got == want, what


# -- the Granite-4.0-H-Micro configuration (PR 65) ---------------------------------

GRANITE_METRICS = ("mlp_ms_per_step", "ssm_share_pct", "stream_rms_final")
GRANITE_APPENDED = NEMOTRON_METRICS


def test_granite_file_has_the_catalogs_keys_and_the_floor_cuts():
    """Every key of the catalog's row under its own name and at its
    published value but the three cut: the depth, the layers' types cut
    to it and the vocabulary, with the published values beside them; no
    width is cut; what the row does not give is stated as assumed."""
    import pathlib

    cell = spec_mod.load_cell(GRANITE_CELL)
    config = cell.config
    path = pathlib.Path(
        "/opt/skills/guides/model-configs/architectures.jsonl")
    if path.exists():
        rows = [json.loads(line) for line in path.read_text().splitlines()]
        entry = next(r for r in rows if r["name"] == "granite-4.0-h-micro")
        catalog = entry["config"]
        assert entry["source_url"] == config["source"]
        assert all(key in config for key in catalog)
        differ = sorted(k for k, v in catalog.items() if config[k] != v)
        assert differ == sorted(config["reduced"])
        assert config["published"] == {k: catalog[k]
                                       for k in config["reduced"]}
        assert catalog["layer_types"][:10] == config["layer_types"]
    assert config["reduced"] == ["num_hidden_layers", "layer_types",
                                 "vocab_size"]
    # the floors: one whole period of ten, an eighth of the rows
    assert (config["num_hidden_layers"], config["layer_types"],
            config["vocab_size"]) == (
                10, ["mamba"] * 5 + ["attention"] + ["mamba"] * 4,
                100352 // 8)
    assert (config["hidden_size"], config["num_attention_heads"],
            config["num_key_value_heads"], config["mamba_n_heads"],
            config["mamba_d_head"], config["mamba_n_groups"],
            config["mamba_d_state"], config["mamba_d_conv"],
            config["intermediate_size"], config["shared_intermediate_size"],
            config["embedding_multiplier"], config["residual_multiplier"],
            config["attention_multiplier"], config["logits_scaling"],
            config["tie_word_embeddings"], config["rms_norm_eps"],
            config["mamba_chunk_size"], config["num_local_experts"]) == (
                2048, 32, 8, 64, 64, 1, 128, 4, 8192, 8192, 12, 0.22,
                0.015625, 8, True, 1e-5, 256, 0)
    assert (config["train_seq"], config["scan_chunk"],
            config["layer_types_here"]) == (
                4096, 128, ",".join(config["layer_types"]))
    entry = next(c for c in cell.bench["configs"]
                 if c["name"] == cell.config_name)
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]
    assert (cell.chips, cell.traffic_name) == (1, "local-msgd-s4k-granite4h")
    assert ["embed", "ssm_proj", "ssm_conv", "ssd_scan", "ssm_norm", "attn",
            "mlp", "head_loss", "update"] == config["scopes"]
    assert (config["reference"], config["arithmetic"]) == (
        "granite_plain", "granite")
    assert all(key in config for key in config["launcher_from"].values())
    assert all(key in config for key in config["tiny"])
    assert len(config["assumed"]) >= 8
    assert "share 0 of stage 0" in config["deployment"] and \
        "Four stages of ten layers" in config["deployment"]
    assert set(config["limits"]) == {"LOSS_TOL_NATS", "GRAD_REL_TOL"}
    worked = [got for _what, got, want in cell.arithmetic().hand_worked()
              if got == want]
    # a mamba layer, an attention layer, the vector
    assert all(count in worked for count in (
        76_182_976, 60_821_504, 772_160_448))
    assert cell.arithmetic().param_count(config) == 772_160_448
    assert cell.reference().LOSS_TOL_NATS > 0 < cell.reference().GRAD_REL_TOL
    # the tiny size: one group, and every multiplier off 1
    small = config["tiny"]
    assert small["mamba_n_groups"] == 1 and small["layer_types"] == [
        "mamba", "mamba", "attention", "mamba"]
    assert all(small[key] != 1 for key in (
        "embedding_multiplier", "residual_multiplier",
        "attention_multiplier", "logits_scaling"))


def test_the_launcher_builds_the_dense_hybrid_from_the_cells_files():
    from chipbench import run as runner
    from mpit_tpu.lm.model import build_kw
    from mpit_tpu.train.launch import lm_trainer_cfg

    cell = spec_mod.load_cell(GRANITE_CELL)
    kw = build_kw(lm_trainer_cfg(runner.launch_config(cell, seed=5)))
    assert (kw["arch"], kw["d_model"], kw["n_heads"], kw["kv_heads"],
            kw["head_dim"], kw["n_layers"], kw["seq_len"], kw["vocab"]) \
        == ("granite", 2048, 32, 8, 0, 10, 4096, 12544)
    assert kw["layer_types"] == cell.arithmetic().layer_types(cell.config)
    assert kw["layer_types"].split(",").count("mamba") == 9
    assert (kw["ssm_heads"], kw["ssm_head_dim"], kw["ssm_groups"],
            kw["ssm_state"], kw["ssm_chunk"], kw["conv_kernel"],
            kw["dense_width"]) == (64, 64, 1, 128, 128, 4, 8192)
    assert (kw["embed_scale"], kw["residual_scale"], kw["attn_scale"],
            kw["logits_scale"], kw["norm_eps"]) == (
                12.0, 0.22, 0.015625, 8.0, 1e-5)


def test_granites_mix_keeps_to_the_traffic_its_issue_fixed():
    """ISSUE 65 fixed the mix before any code was written: the rate one
    of four, the budget a whole number of micro-steps of 4096 tokens,
    momentum 0.9, two rounds of warm-up, closed loop in one process; the
    three new metrics list the cell alone, the five state-space ones
    have it appended, and the budget's table is in the mix's own file."""
    cell = spec_mod.load_cell(GRANITE_CELL)
    mix = cell.traffic
    steps, rest = divmod(mix["token_budget"],
                         mix["batch"] * cell.config["train_seq"])
    assert rest == 0 and steps >= 8
    assert mix["lr"] in (0.003, 0.01, 0.03, 0.1)
    assert (mix["launcher"]["mom"], mix["warmup_rounds"], mix["su"],
            mix["batch"], mix["launcher"]["np"], mix["launcher"]["opt"],
            mix["launcher"]["lm_use_flash"]) == (0.9, 2, 1, 1, 1, "msgd", 1)
    assert "quartile distance" in mix["chosen_because"]
    moves = {m["name"]: m["moves"] for m in cell.metrics("per_layer")}
    assert set(GRANITE_METRICS + GRANITE_APPENDED) <= set(moves)
    assert [moves[m] for m in GRANITE_METRICS] == [
        "tokens_per_s", "tokens_per_s", "loss_at_budget"]
    layers = {m["name"]: m["layer"] for m in cell.bench["per_layer"]}
    assert {layers[m] for m in GRANITE_METRICS} == {layers["ssm_ms_per_step"]}
    units = {m["name"]: (m["unit"], m["better"], m["source"])
             for m in cell.bench["per_layer"]}
    assert units["mlp_ms_per_step"] == ("ms", "lower", "device_trace")
    assert units["ssm_share_pct"] == ("%", "higher", "device_trace")
    assert units["stream_rms_final"] == ("rms", "lower", "program_counter")
    for metric in cell.bench["per_layer"]:
        if metric["name"] in GRANITE_METRICS:
            assert metric["workloads"] == [GRANITE_CELL]
        elif metric["name"] in GRANITE_APPENDED:
            assert metric["workloads"][-1] == GRANITE_CELL
        elif "workloads" in metric:
            assert GRANITE_CELL not in metric["workloads"], metric["name"]
    # added together and in order (PR 66's one and PR 67's three follow)
    assert [m["name"] for m in cell.bench["per_layer"][-7:-4]] == list(
        GRANITE_METRICS)
    assert (cell.bench["configs"][-1]["name"],
            cell.bench["workloads"][-1]["name"]) == (cell.config_name,
                                                     GRANITE_CELL)
    assert cell.chips == 1 and len(cell.why) <= 200
    assert "One stage of four" in cell.why and "1 layer of 10" in cell.why
    assert len(cell.bench["configs"]) == 14 and len(
        cell.bench["workloads"]) == 15 and len(
            cell.bench["per_layer"]) == 94
    # one cell in four may take four chips, and none does
    assert sum(w["chips"] == 4 for w in cell.bench["workloads"]) == 0


def test_granites_readers_find_nothing_in_a_run_without_the_block():
    """What the parent's traced run hands them: a cell whose
    configuration lists no such scope, a program that recorded no such
    counter, no device trace, no merged trace: None, no raise."""
    for name in ("kimi-linear-l5e8-local", "c111m-local"):
        cell = spec_mod.load_cell(name)
        run = {"cell": cell, "reduction": {"step_module": "jit__lambda"},
               "obs_trace": None, "peaks": None, "results": {},
               "summary": {"worker_ranks": [0], "window": [0.0, 1.0]}}
        for metric in GRANITE_METRICS:
            reader = spec_mod.load_reader(cell.root, cell.bench, metric)
            assert reader is not None and reader(dict(run)) is None


def test_granites_readers_read_a_hand_made_run(monkeypatch):
    """The three readers, the five shared state-space ones and the
    metrics without a ``workloads`` list that the cell has to report, on
    a scope table and a span tree made by hand."""
    from chipbench import flops
    from chipbench.layers import spantree

    cell = spec_mod.load_cell(GRANITE_CELL)
    listed = [m["name"] for m in cell.metrics("per_layer")]
    unlisted = [m["name"] for m in cell.bench["per_layer"]
                if "workloads" not in m]
    assert len(unlisted) == 10 and set(unlisted) <= set(listed)
    assert len(listed) == 18

    class Round:
        def __init__(self, k, decay):
            self.args = {"round": k, "lm_stream_rms": [0.5 + 0.1 * k],
                         "lm_ssm_decay_mean": [decay + 0.01 * j
                                               for j in range(-4, 5)]}

    class Tree:
        def rounds(self):
            return [Round(k, 0.88 + 0.01 * k) for k in range(5)]

    table = {"step": 250.0, "embed": 1.0, "ssm_proj": 60.0, "ssm_conv": 12.0,
             "ssd_scan": 22.0, "ssm_norm": 6.0, "attn": 15.0, "mlp": 110.0,
             "head_loss": 11.0, "update": 10.0, "unscoped": 3.0}
    monkeypatch.setattr(spantree, "scope_ms_per_step", lambda run: table)
    monkeypatch.setattr(spantree, "xplane_path", lambda run: None)
    run = {"cell": cell, "peaks": flops.load_peaks("TPU v5 lite"),
           spantree.CACHE_KEY: Tree(),
           "summary": {"tokens_per_s": 12000.0, "worker_ranks": [0]},
           "reduction": {"step_module": "jit__lambda", "step_module_runs": 2,
                         "mosaic_by_scope": {
                             "attn": (6, 0.024), "ssd_scan": (54, 0.040),
                             "update": (2, 0.020)}}}

    def read(name, run=run):
        return spec_mod.load_reader(cell.root, cell.bench, name)(run)

    assert read("mlp_ms_per_step") == pytest.approx(110.0)
    assert read("ssm_ms_per_step") == pytest.approx(100.0)
    assert read("ssm_share_pct") == pytest.approx(100 * 100.0 / 250.0)
    assert read("stream_rms_final") == pytest.approx(0.7)
    assert read("ssd_scan_ms_per_step") == pytest.approx(22.0)
    assert read("ssm_conv_ms_per_step") == pytest.approx(12.0)
    assert read("ssm_decay_mean") == pytest.approx(0.90)
    scan = cell.arithmetic().ssd_scan_cost(cell.config, 1)
    assert read("ssd_scan_roofline") == pytest.approx(
        100 * max(scan["flops"] / 197e12, scan["bytes"] / 819e9) / 0.022)
    # one group: C B^T once, so the yardstick is bound by memory
    assert scan["bytes"] / 819e9 > scan["flops"] / 197e12
    assert read("head_loss_ms_per_step") == pytest.approx(11.0)
    assert read("flash_ms_per_step") == pytest.approx(12.0)
    families = cell.arithmetic().kernels(cell.config, 1)
    assert read("flash_roofline") == pytest.approx(
        100 * families["attn"]["flops"] / 197e12 / 0.012)
    assert read("mfu_pct") == pytest.approx(
        100 * 4_752_863_232 * 12000.0 / 197e12)
    for metric in cell.metrics("per_layer"):
        assert spec_mod.load_reader(cell.root, cell.bench,
                                    metric["name"]) is not None

    # a block that records no stream rms, and a run with no mixer scope
    class Bare:
        def rounds(self):
            rounds = Tree().rounds()
            for r in rounds:
                del r.args["lm_stream_rms"]
            return rounds

    assert read("stream_rms_final", {**run, spantree.CACHE_KEY: Bare()}) \
        is None
    monkeypatch.setattr(spantree, "scope_ms_per_step",
                        lambda run: {"step": 130.0, "attn": 15.0,
                                     "mlp": 110.0})
    assert read("ssm_share_pct") is None
    assert read("mlp_ms_per_step") == pytest.approx(110.0)


def granite_cases():
    return spec_mod.load_cell(GRANITE_CELL).arithmetic().hand_worked()


@pytest.mark.parametrize("what,got,want", granite_cases(),
                         ids=[c[0] for c in granite_cases()])
def test_granite_arithmetic_by_hand_through_the_cell(what, got, want):
    assert got == want, what


def _one_line_fields():
    bench = json.loads((spec_mod.ROOT / "BENCHMARK.json").read_text())
    out = [("command", " ".join(bench["command"]))]
    for entry in bench["configs"]:
        out += [(f"configs.{entry['name']}.why", entry["why"]),
                (f"configs.{entry['name']}.source", entry["source"])]
    out += [(f"workloads.{w['name']}.why", w["why"])
            for w in bench["workloads"]]
    out += [(f"per_layer.{m['name']}.layer", m["layer"])
            for m in bench["per_layer"]]
    return out


@pytest.mark.parametrize("where,text", _one_line_fields(),
                         ids=[f[0] for f in _one_line_fields()])
def test_a_line_of_the_benchmark_file_keeps_to_200_printable_characters(
        where, text):
    # The driver refuses BENCHMARK.json before any run for a `why`, a
    # `layer` or a `source` outside 1 to 200 characters on one line (PR
    # 38's first check: the new cell's `why` had 220).
    assert 1 <= len(text) <= 200, (where, len(text))
    assert text.isprintable(), where


# -- the host copies' readers on a recorded trace (PR 48) ----------------------

COPIES_FIXTURE = spec_mod.ROOT / "chipbench" / "fixtures" / "copies2.obs_trace.json"
COPIES_EXPECTED = json.loads(
    (spec_mod.ROOT / "chipbench" / "fixtures" / "copies2.expected.json").read_text())
PS_CELLS = ["c111m-ps1w-su1", "c1.3b-ps1w-su8", "olmoe-l1-ps1w-su1"]


def copies_run():
    """The ``run`` a reader is given, for the two rounds cut out of a
    traced run of ``c111m-ps1w-su1`` on the chip
    (``chipbench/fixtures/trim_obs_trace.py``)."""
    fixture = json.loads(COPIES_FIXTURE.read_text())["otherData"]["fixture"]
    return {"obs_trace": str(COPIES_FIXTURE), "results": {}, "reduction": {},
            "summary": {"window": fixture["window"],
                        "worker_ranks": fixture["worker_ranks"]}}


@pytest.mark.parametrize("name", sorted(COPIES_EXPECTED["numbers"]))
def test_a_host_copies_reader_keeps_its_number_on_the_recorded_rounds(name):
    """Every new metric's reader runs on the committed cut and gives
    what it gave when the cut was made: the arithmetic of the yardstick
    is held by a recorded trace, not only by a hand-made one.  (The cut
    brings no device trace, so the idle time's reader finds nothing.)"""
    bench = spec_mod.load_bench(spec_mod.ROOT)
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    assert entry["workloads"] == PS_CELLS
    perf = (spec_mod.ROOT / "PERF.md").read_text()
    layers = perf[perf.index("## 3. Layers"):perf.index("## 4. Cells")]
    assert f"| {entry['layer']} |" in layers and f"`{name}`" in layers
    value = spec_mod.load_reader(spec_mod.ROOT, bench, name)(copies_run())
    want = COPIES_EXPECTED["numbers"][name]
    if want is None:
        assert value is None and name == "idle_by_host_pass_pct"
    else:
        assert value == pytest.approx(want, rel=1e-9)


def test_the_recorded_rounds_are_two_whole_rounds_of_the_c111m_cell():
    from chipbench.layers import copytree
    from mpit_tpu.obs import trace as obs_trace

    obs_trace.validate_trace(str(COPIES_FIXTURE))
    copies = copytree.load(copies_run())
    assert [r.args["round"] for r, _m, _p in copies.rounds] == (
        COPIES_EXPECTED["rounds"])
    cell = spec_mod.load_cell(PS_CELLS[0])
    vector = 4 * cell.arithmetic().param_count(cell.config)
    for _r, mine, pieces in copies.rounds:
        assert copies.prog.vector_bytes(1, mine) == vector
        assert sum(c.moved for c in mine) == 17 * vector
        assert sum(s.args["bytes"] for s in pieces
                   if s.name == "h2d") == vector


# -- the upload that follows its landing (PR 49) -------------------------------

def _pull_early(run):
    bench = spec_mod.load_bench(spec_mod.ROOT)
    return spec_mod.load_reader(spec_mod.ROOT, bench, "pull_early_pct")(run)


def test_pull_early_pct_is_entered_for_the_ps_cells_under_a_layer_of_perf_md():
    bench = spec_mod.load_bench(spec_mod.ROOT)
    entry = next(m for m in bench["per_layer"] if m["name"] == "pull_early_pct")
    assert entry == {"name": "pull_early_pct", "unit": "%", "better": "higher",
                     "source": "program_span", "layer": "L3 shell + client",
                     "moves": "tokens_per_s", "workloads": PS_CELLS}
    # appended, nothing moved; PR 51's four, PR 53's four, PR 58's five,
    # PR 61's six, PR 65's three and PR 66's one follow it
    assert bench["per_layer"][-27] is entry
    perf = (spec_mod.ROOT / "PERF.md").read_text()
    layers = perf[perf.index("## 3. Layers"):perf.index("## 4. Cells")]
    assert f"| {entry['layer']} |" in layers and "`pull_early_pct`" in layers


def test_pull_early_pct_reads_0_on_the_rounds_recorded_before_the_change():
    """The committed cut is of PR 48's program: each shard went up after
    its PARAM op was done (round 69: shard 0's ``rx`` ends at ..142.0 ms
    and its first piece is dispatched at ..154.2), so no byte of either
    round was early."""
    assert _pull_early(copies_run()) == 0.0


@pytest.mark.parametrize("early", [(0, 0), (10, 5), (34, 0), (34, 38)],
                         ids=["none", "some_of_each", "all_of_shard_0",
                              "every_piece"])
def test_pull_early_pct_reads_the_hand_computed_share(early, tmp_path):
    """The same two rounds with the dispatch of the first ``early[s]``
    pieces of shard ``s`` moved to just before the end of that shard's
    own PARAM ``rx`` span on the worker (servers 0 and 2 are shards 0 and
    1): those pieces' bytes over the round's 598,468,608, by hand."""
    trace = json.loads(COPIES_FIXTURE.read_text())
    events = trace["traceEvents"]
    ends, open_rx = {}, {}
    for ev in events:
        if ev.get("cat") == "wire" and ev["name"] == "rx" and ev["pid"] == 1:
            if ev["ph"] == "B":
                open_rx[ev["tid"]] = (ev["args"]["round"], ev["args"]["peer"])
            else:
                ends[open_rx.pop(ev["tid"])] = ev["ts"]
    assert sorted(ends) == [(69, 0), (69, 2), (70, 0), (70, 2)]
    moved, counts, begun = 0, {}, None
    for ev in events:
        if ev.get("cat") != "copy" or ev["name"] != "h2d":
            continue
        if ev["ph"] == "B":
            k, shard = ev["args"]["round"], ev["args"]["shard"]
            n = counts[k, shard] = counts.get((k, shard), 0) + 1
            begun = None
            if n <= early[shard]:
                begun = ev["ts"] = ends[k, 2 * shard] - 1000.0 + n
                moved += ev["args"]["bytes"]
        elif begun is not None:
            ev["ts"] = begun + 0.5
    assert set(counts.values()) == {34, 38}  # pieces a shard
    path = tmp_path / "moved.obs_trace.json"
    path.write_text(json.dumps(trace))
    run = dict(copies_run(), obs_trace=str(path))
    by_hand = 100.0 * (moved / 2) / 598_468_608  # the same in both rounds
    assert moved / 2 == sum(
        n * 8_388_608 for n in early) - (1_820_672 if early[0] == 34 else 0) - (
        3_690_496 if early[1] == 38 else 0)  # a shard's last piece is short
    assert _pull_early(run) == pytest.approx(by_hand, rel=1e-12)
    if early == (34, 38):
        assert by_hand == 100.0


# -- the ring copies split over threads (PR 66) --------------------------------

def _copy_split(run):
    bench = spec_mod.load_bench(spec_mod.ROOT)
    return spec_mod.load_reader(spec_mod.ROOT, bench, "copy_split_pct")(run)


def test_copy_split_pct_is_entered_last_for_the_ps_cells_under_a_layer_of_perf_md():
    bench = spec_mod.load_bench(spec_mod.ROOT)
    entry = bench["per_layer"][-4]  # PR 67 appended three
    assert entry == {"name": "copy_split_pct", "unit": "%", "better": "higher",
                     "source": "program_span", "layer": "L2 servers + wire",
                     "moves": "tokens_per_s", "workloads": PS_CELLS}
    perf = (spec_mod.ROOT / "PERF.md").read_text()
    layers = perf[perf.index("## 3. Layers"):perf.index("## 4. Cells")]
    assert f"| {entry['layer']} |" in layers and "`copy_split_pct`" in layers


def test_copy_split_pct_reads_nothing_on_the_rounds_recorded_before_the_change():
    """The committed cut is of PR 48's program, whose ``wire`` spans say
    nothing of parts: the reader returns None and does not raise, as it
    must on this PR's parent."""
    assert _copy_split(copies_run()) is None


@pytest.mark.parametrize("ends,share", [((), 0.0), (("tx",), 0.5),
                                        (("tx", "rx"), 1.0)],
                         ids=["no_helper", "one_end", "both_ends"])
def test_copy_split_pct_reads_the_hand_computed_share(ends, share, tmp_path):
    """The same two rounds with ``split_bytes`` written on every ``wire``
    span (0, or all but a last chunk of 1,000,000 bytes on the named
    ends): each message has a ``tx`` and an ``rx`` of the same bytes, so
    the named ends' share of the round's bytes less the short chunks, by
    hand."""
    trace = json.loads(COPIES_FIXTURE.read_text())
    spans = [ev for ev in trace["traceEvents"]
             if ev.get("cat") == "wire" and ev["ph"] == "B"]
    assert len(spans) == 16  # two rounds x (GRAD, PARAM) x two servers x ends
    for ev in spans:
        ev["args"]["split_bytes"] = (
            ev["args"]["bytes"] - 1_000_000 if ev["name"] in ends else 0)
    assert sum(ev["args"]["bytes"] for ev in spans) == 2 * 4 * 598_468_608
    path = tmp_path / "split.obs_trace.json"
    path.write_text(json.dumps(trace))
    by_hand = 100.0 * (share - len(ends) * 4 * 1_000_000 / (4 * 598_468_608))
    assert _copy_split(dict(copies_run(), obs_trace=str(path))) == (
        pytest.approx(by_hand, rel=1e-12))

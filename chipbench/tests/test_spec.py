"""Tests of the benchmark's own harness, on the CPU:

    JAX_PLATFORMS=cpu python3 -m pytest chipbench/tests -q

They live under the benchmark's directory because a ``benchmark`` PR may
add files nowhere else (ISSUE 25 asked for ``tests/test_chipbench_spec.py``;
PERF.md section 7 queues the move for a PR that may touch ``tests/``).
What they hold: a configuration's reference and arithmetic are found by
its own keys and nothing falls back; the GPT-2 arithmetic gives the
hand-worked numbers on the committed files; the recorded fixture's
Mosaic numbers are unchanged and belong to no family; the hand-made
scoped trace is booked by scope, a call under two scopes for neither;
the reference's new signature is the old call to the bit; and no file of
the harness names a block's module or reads a block's size key.
"""

import json
import pathlib
import re

import pytest

from chipbench import flops, spec as spec_mod
from chipbench.fixtures import handmade

FIXTURES = spec_mod.ROOT / "chipbench" / "fixtures"
CELLS = [w["name"] for w in spec_mod.load_bench()["workloads"]]


# -- found by name, never by default -----------------------------------------


@pytest.mark.parametrize("name", CELLS)
def test_a_cell_finds_its_reference_and_arithmetic_by_its_configurations_keys(name):
    cell = spec_mod.load_cell(name)
    reference, arithmetic = cell.reference(), cell.arithmetic()
    assert pathlib.Path(reference.__file__).name == \
        cell.config["reference"] + ".py"
    assert pathlib.Path(arithmetic.__file__).name == \
        cell.config["arithmetic"] + ".py"
    assert callable(reference.loss_and_grad_flat)
    assert reference.LOSS_TOL_NATS > 0 and reference.GRAD_REL_TOL > 0
    families = arithmetic.kernels(cell.config, int(cell.traffic["batch"]))
    assert {k["scope"] for k in families.values()} <= set(cell.config["scopes"])


def minimal_root(tmp_path, **config):
    """A benchmark of one cell under ``tmp_path`` whose configuration's
    file is ``config`` over the committed 111m one."""
    base = spec_mod.load_cell("c111m-local")
    bench = {**base.bench, "workloads": [base.bench["workloads"][1]],
             "configs": [base.bench["configs"][0]]}
    merged = {**base.config, **config}
    merged = {k: v for k, v in merged.items() if v is not None}
    (tmp_path / "chipbench" / "configs").mkdir(parents=True)
    (tmp_path / "chipbench" / "traffic").mkdir()
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    (tmp_path / bench["configs"][0]["file"]).write_text(json.dumps(merged))
    (tmp_path / "chipbench" / "traffic" / "local-msgd.json").write_text(
        json.dumps(base.traffic))
    return tmp_path


@pytest.mark.parametrize("key", spec_mod.CONFIG_KEYS)
def test_a_configuration_without_a_contract_key_is_a_spec_error(tmp_path, key):
    root = minimal_root(tmp_path, **{key: None})
    with pytest.raises(spec_mod.SpecError, match=key):
        spec_mod.load_cell("c111m-local", root=root)


@pytest.mark.parametrize("kind", ["reference", "arithmetic"])
def test_a_named_module_that_is_not_there_is_a_spec_error(tmp_path, kind):
    root = minimal_root(tmp_path, **{kind: "nowhere"})
    cell = spec_mod.load_cell("c111m-local", root=root)
    with pytest.raises(spec_mod.SpecError, match="nowhere"):
        getattr(cell, kind)()
    with pytest.raises(spec_mod.SpecError):
        spec_mod.load_named(root / "chipbench", kind, {"name": "x"})
    with pytest.raises(spec_mod.SpecError):
        spec_mod.load_named(root / "chipbench", kind, {kind: "../spec"})


def test_a_module_under_another_root_is_the_one_that_is_loaded(tmp_path):
    root = minimal_root(tmp_path, arithmetic="mine")
    (root / "chipbench" / "arithmetic").mkdir()
    (root / "chipbench" / "arithmetic" / "mine.py").write_text(
        "def param_count(c):\n    return 7\n")
    cell = spec_mod.load_cell("c111m-local", root=root)
    assert cell.arithmetic().param_count(cell.config) == 7
    assert flops.exchange_bytes_per_round(cell) == 56


# -- the arithmetic ------------------------------------------------------------


def test_gpt2_arithmetic_gives_its_hand_worked_numbers():
    from chipbench.arithmetic import gpt2

    cases = gpt2.hand_worked()
    assert len(cases) >= 10
    assert [what for what, got, want in cases if got != want] == []


@pytest.mark.parametrize("cell_name, params, flops_per_token", [
    ("c111m-ps1w-su1", 149_617_152, 750_675_456),
    ("c111m-local", 149_617_152, 750_675_456),
    ("c1.3b-ps1w-su8", 411_451_392, None),
])
def test_the_committed_files_give_the_exchanged_vector_and_the_flops(
        cell_name, params, flops_per_token):
    cell = spec_mod.load_cell(cell_name)
    arithmetic = cell.arithmetic()
    assert arithmetic.param_count(cell.config) == params
    assert flops.exchange_bytes_per_round(cell) == 8 * params
    if flops_per_token is not None:
        assert arithmetic.train_flops_per_token(cell.config) == flops_per_token
    peaks = flops.load_peaks("TPU v5 lite")
    want = (100.0 * arithmetic.train_flops_per_token(cell.config) * 1000.0
            / (peaks["bf16_tflops"] * 1e12))
    assert flops.mfu_pct(cell, 1000.0, 1, peaks) == pytest.approx(want)
    (family,) = arithmetic.kernels(cell.config, 6).values()
    assert family["scope"] == "attn"
    assert family["least_calls"] == 2 * cell.config["n_layer"]


def test_the_vocabulary_reaches_the_program_through_the_launcher():
    from chipbench import run as runner

    for name in CELLS:
        cell = spec_mod.load_cell(name)
        cfg = runner.launch_config(cell, seed=5)
        assert cfg.lm_vocab == cell.config["vocab_size"] == 50257


# -- Mosaic time by kernel ------------------------------------------------------


def test_the_recorded_fixture_keeps_its_numbers_and_books_no_family():
    from chipbench.reduce import reduce_trace

    want = json.loads((FIXTURES / "steps4.expected.json").read_text())
    red = reduce_trace(str(FIXTURES / "steps4.xplane.pb"), "jit_loss",
                       handmade.SCOPES)
    assert red["mosaic_s"] == pytest.approx(want["numbers"]["mosaic_s"],
                                            rel=1e-12)
    assert red["mosaic_calls"] == want["numbers"]["mosaic_calls"] == 16
    assert red["mosaic_by_scope"] == {}
    assert sum(calls for _l, calls, _s in red["mosaic_no_family"]) == 16
    assert sum(s for _l, _c, s in red["mosaic_no_family"]) == \
        pytest.approx(red["mosaic_s"], rel=1e-12)
    # without a scope list nothing is read from the metadata at all
    bare = reduce_trace(str(FIXTURES / "steps4.xplane.pb"), "jit_loss")
    assert bare["mosaic_s"] == red["mosaic_s"]
    assert bare["mosaic_by_scope"] == {}


@pytest.fixture
def scoped(tmp_path):
    from chipbench.reduce import reduce_trace

    path = handmade.write_scoped(tmp_path / "scoped.xplane.pb")
    return reduce_trace(str(path), "jit_loss", handmade.SCOPES)


@pytest.mark.parametrize("key", sorted(handmade.SCOPED_EXPECTED))
def test_the_hand_made_trace_reduces_to_its_hand_checked_numbers(scoped, key):
    want = handmade.SCOPED_EXPECTED[key]
    if key == "mosaic_no_family":
        assert [(l, c) for l, c, _s in scoped[key]] == \
            [(l, c) for l, c, _s in want]
        assert [s for _l, _c, s in scoped[key]] == pytest.approx(
            [s for _l, _c, s in want], rel=1e-9)
    elif key == "mosaic_by_scope":
        assert {k: v[0] for k, v in scoped[key].items()} == \
            {k: v[0] for k, v in want.items()}
        assert {k: v[1] for k, v in scoped[key].items()} == pytest.approx(
            {k: v[1] for k, v in want.items()}, rel=1e-9)
    else:
        assert scoped[key] == pytest.approx(want, rel=1e-9)


def test_a_call_under_two_scopes_or_two_stacks_counts_for_neither(scoped):
    # 11 us under mlp/.../attn and 5 us of a name two programs claim, in
    # each of two runs: in neither family, and in the sum of all of them
    booked = sum(s for _c, s in scoped["mosaic_by_scope"].values())
    unbooked = sum(s for _l, _c, s in scoped["mosaic_no_family"])
    assert scoped["mosaic_by_scope"]["attn"][1] == pytest.approx(160e-6)
    assert "mlp" not in scoped["mosaic_by_scope"]
    assert booked + unbooked == pytest.approx(scoped["mosaic_s"], rel=1e-9)
    assert sum(c for c, _s in scoped["mosaic_by_scope"].values()) + \
        sum(c for _l, c, _s in scoped["mosaic_no_family"]) == \
        scoped["mosaic_calls"]


def test_the_flash_readers_read_the_attn_family_alone(scoped):
    """Per micro-step of the hand-made trace: attn 80 us, all Mosaic
    kernels 123 us.  At batch 2 the 111m attention needs 2 x 12 x 2048 x
    2049 / 2 pairs x 896 FLOPs x 10 layers = 451.19 GFLOP, 2.2903 ms at
    197 TFLOP/s (its bytes, 1.64 GB, need 2.0 ms: compute binds), so the
    share of 80 us is 2862.9%: a made-up time, a real formula."""
    cell = spec_mod.load_cell("c111m-local")
    cell.traffic["batch"] = 2
    run = {"cell": cell, "reduction": scoped,
           "peaks": flops.load_peaks("TPU v5 lite")}
    ms = spec_mod.load_reader(cell.root, cell.bench, "flash_ms_per_step")(run)
    assert ms == pytest.approx(0.08)
    share = spec_mod.load_reader(cell.root, cell.bench, "flash_roofline")(run)
    need = 10 * 896 * (2 * 12 * 2048 * 2049 / 2)
    assert share == pytest.approx(100 * need / 197e12 / 80e-6)
    # a trace whose calls carry no scope gives both readers nothing
    run["reduction"] = {**scoped, "mosaic_by_scope": {}}
    assert spec_mod.load_reader(cell.root, cell.bench,
                                "flash_ms_per_step")(run) is None
    assert spec_mod.load_reader(cell.root, cell.bench,
                                "flash_roofline")(run) is None
    assert flops.kernel_family(run, "no-such-family") is None


def test_scopes_come_from_the_cells_configuration():
    from chipbench.layers import spantree

    cell = spec_mod.load_cell("c111m-local")
    cell.config["scopes"] = ["router", "experts"]
    assert spantree.model_scopes({"cell": cell}) == ["router", "experts"]
    # a run that names no cell: every scope a committed configuration lists
    assert spantree.model_scopes({}) == ["embed", "attn", "mlp", "head_loss",
                                         "update"]


# -- the reference ---------------------------------------------------------------


def test_the_references_new_signature_is_the_old_call_to_the_bit():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from chipbench import compare, run as runner
    from chipbench.reference import gpt_plain
    from chipbench.traffic.packed_bytes import packed_batch

    cell = spec_mod.load_cell("c111m-local")
    cell.config.update(cell.config["tiny"])
    cell.traffic["launcher"].update(device_policy="cpu", lm_use_flash=0)
    flat = runner.build_model(cell, seed=11).flat
    assert int(flat.w0.size) == cell.arithmetic().param_count(cell.config)
    tokens = jnp.asarray(packed_batch(11, 0, 2, cell.config["n_positions"]))
    heads, depth = cell.config["n_head"], cell.config["n_layer"]
    old = jax.jit(jax.value_and_grad(lambda w, tok: gpt_plain.loss(
        flat.unravel(w), tok, heads, depth)))
    with jax.default_matmul_precision("highest"):
        old_loss, old_grad = old(flat.w0, tokens)
    new_loss, new_grad = cell.reference().loss_and_grad_flat(
        flat.w0, flat.unravel, tokens, cell.config)
    assert float(new_loss) == float(old_loss)
    assert np.array_equal(np.asarray(new_grad), np.asarray(old_grad))
    # and the one comparison holds it by the module's own tolerances
    same = compare.compare(new_loss, new_grad, old_loss, old_grad, gpt_plain)
    assert same["ok"] and same["grad_rel_err"] == 0.0
    assert (same["loss_tol"], same["grad_tol"]) == (1.5e-3, 1.3e-2)
    off = compare.compare(new_loss + 2e-3, new_grad, old_loss, old_grad,
                          gpt_plain)
    assert not off["ok"]
    off = compare.compare(new_loss, new_grad * 1.02, old_loss, old_grad,
                          gpt_plain)
    assert not off["ok"] and off["grad_rel_err"] == pytest.approx(0.02, rel=1e-3)


# -- nothing in the harness knows a block ------------------------------------------


BLOCK_NAMES = re.compile(r"gpt_plain|n_embd|n_inner|n_head|n_layer")


def test_no_file_of_the_harness_names_a_blocks_module_or_size_key():
    root = spec_mod.ROOT / "chipbench"
    files = sorted(root.glob("*.py")) + sorted((root / "layers").glob("*.py"))
    assert len(files) > 25
    found = [f"{path.name}:{n}: {line.strip()}"
             for path in files
             for n, line in enumerate(path.read_text().splitlines(), 1)
             if BLOCK_NAMES.search(line)]
    assert found == []
    assert "set_vocab" not in (root / "child.py").read_text()

"""The table of blocks (``mpit_tpu/lm/archs.py``) and what is derived
from it: the launcher's switches, the trainer's config and ``build``.

No list of which block takes which size is kept here: the table is the
list.  ``OTHER`` has one value a size that is not its default (a size
the table has and this has not fails ``test_the_table_is_whole``), and
``TRANSLATED`` says where a size went that is no field of its own name
on the module.
"""

import json
import pathlib
import subprocess
import sys

import pytest

from mpit_tpu.lm import archs
from mpit_tpu.lm.model import build, build_kw
from mpit_tpu.lm.trainer import LM_DEFAULTS
from mpit_tpu.train.launch import LAUNCH_DEFAULTS, lm_trainer_cfg

ROOT = pathlib.Path(__file__).resolve().parent.parent
CONFIGS = sorted((ROOT / "chipbench" / "configs").glob("*.json"))

# a value a size, not its default, that makes a block together with the
# others' whichever block takes them
OTHER = dict(
    d_model=96, n_heads=6, n_layers=3, seq_len=32, vocab=320,
    n_experts=4, experts_per_tok=1, expert_width=16, experts_first=1,
    experts_held=2, route_scale=2.5, dense_layers=1, dense_width=48,
    rope_theta=5000.0, norm_eps=1e-6, kv_heads=2, head_dim=8,
    window=8, full_every=2, yarn_factor=4.0, yarn_orig=16,
    yarn_beta_fast=16.0, yarn_beta_slow=2.0, yarn_attn_factor=1.25,
    layer_types="conv,full_attention,conv", conv_kernel=4,
    loop_steps=2, exit_beta=0.05, exit_bias=-2.0,
    q_rank=24, kv_rank=16, qk_nope=8, qk_rope=4, v_head=8,
    shared_experts=2, mtp_layers=0, mtp_weight=0.125,
    kda_heads=3, kda_head_dim=8,
    index_heads=3, index_head_dim=4, index_topk=8,
    block_len=8, mask_id=300, noise_seed=9,
    bias_rate=0.002, embed_scale=4.0,
    ssm_heads=6, ssm_head_dim=4, ssm_groups=3, ssm_state=8, ssm_chunk=16,
    shared_width=24, init_depth=7,
    gdn_key_heads=3, gdn_value_heads=6, gdn_key_dim=8, gdn_value_dim=4,
    rotary_factor=0.5,
    residual_scale=0.5, attn_scale=0.25, logits_scale=2.0)
# where a block cannot take ``OTHER``'s value of a size: its own
OTHER_OF = {"granite": {"layer_types": "mamba,attention,mamba"},
            "kimi": {"layer_types": "kda,full_attention,kda"},
            "nemotron": {"layer_types": "mamba,attention,moe"},
            "qwen3next": {"layer_types": "linear_attention,full_attention,"
                                         "linear_attention"},
            "trinity": {"layer_types": "sliding_attention,full_attention,"
                                       "sliding_attention"}}

# a size that is no field of its name on the module: where the maker put it
TRANSLATED = {
    "seq_len": lambda model: model.seq_len,
    "n_layers": lambda model: len(model.module.layer_types),
    "yarn_factor": lambda model: model.module.yarn[0],
    "yarn_orig": lambda model: model.module.yarn[1],
    "yarn_beta_fast": lambda model: model.module.yarn[2],
    "yarn_beta_slow": lambda model: model.module.yarn[3],
    "yarn_attn_factor": lambda model: model.module.yarn[4],
}


def test_the_table_is_whole():
    """Every size a block names has its entry, every entry is some
    block's, a default is a scalar the command line can set, and
    ``OTHER`` has another value of the same type for each."""
    taken = set(archs.SHARED).union(*(b.sizes for b in archs.BLOCKS.values()))
    assert taken == set(archs.SIZES) == set(OTHER)
    for name, size in archs.SIZES.items():
        assert type(size.default) in (int, float, str) and size.doc
        assert type(OTHER[name]) is type(size.default)
        assert OTHER[name] != size.default
    for arch, block in archs.BLOCKS.items():
        names = archs.sizes_of(arch)
        assert len(set(names)) == len(names), arch
    assert archs.ARCHS == tuple(archs.BLOCKS)
    assert len(set(archs.SWITCHES.values())) == len(archs.SIZES)


@pytest.mark.parametrize("arch", archs.ARCHS)
def test_every_size_reaches_its_blocks_module_from_its_switch(arch):
    """``--lm_<size>`` -> ``LAUNCH_DEFAULTS.merged`` -> ``lm_trainer_cfg``
    -> ``build_kw`` -> ``build``: each size of the block, set to a value
    that is not its default, is the built module's field of that name
    (or where ``TRANSLATED`` says), and no other block's size is among
    ``build``'s keywords."""
    names = archs.sizes_of(arch)
    other = {**OTHER, **OTHER_OF.get(arch, {})}
    cfg = LAUNCH_DEFAULTS.merged(
        lm_arch=arch, seed=11,
        **{archs.SWITCHES[name]: other[name] for name in names})
    kw = build_kw(lm_trainer_cfg(cfg))
    assert kw == {"arch": arch, "seed": 11,
                  **{name: other[name] for name in names}}
    model = build(use_flash=False, **kw)
    for name in names:
        if hasattr(model.module, name):
            reached = getattr(model.module, name)
        else:
            reached = TRANSLATED[name](model)
        if name == "layer_types":
            reached = ",".join(reached)
        assert reached == other[name], name
        assert type(reached) is type(other[name]), name
    assert model.vocab == other["vocab"]


@pytest.mark.parametrize("path", CONFIGS, ids=lambda path: path.stem)
def test_a_configuration_names_only_its_own_blocks_switches(path):
    """Every switch a benchmark configuration's ``launcher`` and
    ``launcher_from`` name is the launcher's, and where it is a size's
    it is a size every block takes or one of that file's block."""
    config = json.loads(path.read_text())
    switches = {**config.get("launcher", {}), **config["launcher_from"]}
    arch = switches.get("lm_arch", LAUNCH_DEFAULTS["lm_arch"])
    size_of = {switch: name for name, switch in archs.SWITCHES.items()}
    for switch in switches:
        assert switch in LAUNCH_DEFAULTS, switch
        if switch in size_of:
            assert size_of[switch] in archs.sizes_of(arch), (arch, switch)
    # and ``launcher_from`` maps sizes, nothing else
    assert set(config["launcher_from"]) <= set(size_of)


@pytest.mark.parametrize("arch, stray", [
    ("gpt2", "n_experts"), ("olmoe", "kv_heads"), ("lfm2", "window"),
    ("ouro", "n_experts"), ("joyai", "kv_heads"), ("mellum", "nonsense"),
    ("kimi", "mtp_layers"), ("qwen3next", "kda_heads"),
    ("granite", "n_experts"),
])
def test_build_refuses_a_size_the_block_does_not_take(arch, stray):
    with pytest.raises(TypeError) as refused:
        build(arch=arch, use_flash=False, **{stray: 1})
    said = str(refused.value)
    assert f"{arch} takes no {stray}" in said
    assert all(name in said for name in archs.sizes_of(arch))


@pytest.mark.parametrize("arch", archs.ARCHS)
def test_the_launchers_path_and_the_trainers_give_the_same_model(arch):
    """The guard against the drift that five lists allowed: a block
    built from the launcher's defaults is the block built from the
    trainer's, size for size and type for type."""
    launched = build_kw(lm_trainer_cfg(LAUNCH_DEFAULTS.merged(lm_arch=arch)))
    trained = build_kw(LM_DEFAULTS.merged(arch=arch))
    assert launched == trained
    assert {k: type(v) for k, v in launched.items()} \
        == {k: type(v) for k, v in trained.items()}
    assert set(launched) == {"arch", "seed", *archs.sizes_of(arch)}
    # and both are the table's defaults, which ``build`` fills alike
    assert archs.resolve(arch, {}) == archs.resolve(
        arch, {k: v for k, v in launched.items()
               if k not in ("arch", "seed")})


@pytest.mark.parametrize("arch", [
    name for name in archs.ARCHS
    if archs.block(name).loss == archs.OWN_LOSS])
def test_a_block_with_its_own_loss_is_seeded_by_one_compiled_program(arch):
    """``build`` seeds such a block under ``jit``, where the forward
    pass that ``init`` traces is dead code: the vector is the eager
    ``init``'s to the last bit but one (the compiler may fold an
    initialiser's two scalings into one), leaf for leaf."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    sizes = {name: {**OTHER, **OTHER_OF.get(arch, {})}[name]
             for name in archs.sizes_of(arch)}
    model = build(arch=arch, use_flash=False, seed=5, **sizes)
    sample = jnp.zeros(
        (1, archs.block(arch).sample_len or sizes["seq_len"]), jnp.int32)
    eager = model.module.init(jax.random.PRNGKey(5), sample, sample)
    mine = model.flat.unravel(model.flat.w0)
    assert jax.tree_util.tree_structure(eager["params"]) \
        == jax.tree_util.tree_structure(mine)
    for plain, got in zip(jax.tree_util.tree_leaves(eager["params"]),
                          jax.tree_util.tree_leaves(mine)):
        np.testing.assert_allclose(got, plain, rtol=1e-6, atol=1e-7)


def test_the_launchers_defaults_load_no_model_and_no_trainer():
    """The gang's parent imports ``train/launch.py`` for its defaults:
    the table comes with it, the LM's model and trainer do not; and
    ``plan`` stays the function whatever was imported first."""
    code = (
        "import sys, mpit_tpu.train.launch as launch\n"
        "assert 'mpit_tpu.lm.archs' in sys.modules\n"
        "assert not {'mpit_tpu.lm.model', 'mpit_tpu.lm.trainer',\n"
        "            'mpit_tpu.lm.data'} & set(sys.modules)\n"
        "assert launch.LAUNCH_DEFAULTS['lm_exit_bias'] == 0.0\n"
        "import mpit_tpu.lm.plan\n"
        "from mpit_tpu.lm import LmTrainer, build, plan\n"
        "assert callable(plan) and callable(build)\n")
    subprocess.run([sys.executable, "-c", code], cwd=ROOT, check=True,
                   timeout=120)

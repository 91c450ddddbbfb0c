"""Long-context causal-LM training CLI — the sequence-parallel workload
launcher.

The reference's launchers drive conv/pool workloads (mlaunch/plaunch);
this is the rebuild's beyond-parity long-context analog: TinyDecoder
over a ``(dp, sp)`` device mesh — batch sharded over ``dp``, the
sequence axis ring-sharded over ``sp``
(:func:`mpit_tpu.parallel.ring_attention.ring_attention` with
``batch_axis="dp"``), local pallas flash attention when ``sp == 1``.
Parameters are replicated; gradients reduce across the mesh inside one
jitted step; the update is the fused Nesterov sweep.

Data is a byte corpus: ``--text_file`` (trained as raw bytes, vocab
256) or a deterministic synthetic stream.  Example (8 virtual devices,
2-way data x 4-way sequence parallel):

    python -m mpit_tpu.train.lm_launch --dp 2 --sp 4 --seq_len 2048 \
        --d_model 256 --n_layers 2 --steps 100

Multi-host: same ``--hostfile`` / ``--coordinator`` surface as
mesh_launch; each process feeds its own dp rows.
"""

from __future__ import annotations

import functools
import json
import pathlib
import sys
import time
from typing import List, Optional

from mpit_tpu.utils.config import Config
from mpit_tpu.utils.logging import get_logger

LM_LAUNCH_DEFAULTS = Config(
    seq_len=1024,
    d_model=256,
    n_heads=8,
    n_layers=2,
    batch=8,  # global batch (rows sharded over dp)
    steps=200,
    lr=1e-3,
    mom=0.9,
    dp=0,  # 0 -> 1 (all devices on sp)
    sp=0,  # 0 -> all remaining devices
    # Causal ring layout: zigzag is the default because the causal ring's
    # wall clock is set by its busiest device and the zigzag (early+late
    # half-chunk) layout cuts that device's work; the default came from
    # a July 2026 sweep the ledger has not reproduced.  contiguous
    # remains for ablation.
    layout="zigzag",  # zigzag | contiguous
    attn_dtype="bfloat16",  # kernel input dtype: bfloat16 | float32
    text_file="",
    compile_cache=1,  # persistent XLA compilation cache (utils.platform)
    seed=1,
    log_every=20,
    ckpt_dir="",
    ckpt_every=100,  # steps
    resume="",  # "auto" -> <ckpt_dir>/lm_latest.npz
    # multi-host bootstrap (parallel.distributed.bootstrap)
    hostfile="",
    coordinator="",
    num_processes=0,
    process_id=-1,
)


_SYNTH_CACHE: dict = {}


def _corpus_key(text_file: str) -> str:
    """Identity of the training corpus for resume guards: the resolved
    path ("" for the synthetic stream).  Stored resolved at save time so
    the comparison is cwd-independent."""
    return str(pathlib.Path(text_file).resolve()) if text_file else ""


def _corpus(cfg: Config, log) -> "np.ndarray":
    import numpy as np

    if cfg.text_file:
        data = np.frombuffer(
            pathlib.Path(cfg.text_file).read_bytes(), np.uint8
        ).astype(np.int32)
        log.info("corpus: %s (%d bytes)", cfg.text_file, len(data))
    else:
        # Markov-ish synthetic bytes: learnable structure, not uniform
        # noise.  Deterministic in n — memoized, the scalar chain costs
        # ~1.5s/MB and every run() call would otherwise regenerate it.
        n = max(1 << 20, 8 * (cfg.seq_len + 1) * cfg.batch)
        data = _SYNTH_CACHE.get(n)
        if data is None:
            rng = np.random.default_rng(1234)
            trans = rng.integers(0, 256, (256, 4))
            data = np.empty(n, np.int32)
            data[0] = 0
            choices = rng.integers(0, 4, n)
            noise = rng.random(n)
            resets = rng.integers(0, 256, n)
            for i in range(1, n):
                data[i] = (trans[data[i - 1], choices[i]]
                           if noise[i] > 0.1 else resets[i])
            _SYNTH_CACHE[n] = data
        log.info("corpus: synthetic markov bytes (%d)", n)
    if len(data) < cfg.batch * (cfg.seq_len + 1):
        raise ValueError(
            f"corpus of {len(data)} tokens < one global batch "
            f"({cfg.batch} x {cfg.seq_len + 1})"
        )
    return data


def run(cfg: Config) -> dict:
    from mpit_tpu.parallel.distributed import bootstrap

    pg = bootstrap(
        coordinator=cfg.coordinator or None,
        num_processes=cfg.num_processes or None,
        process_id=cfg.process_id if cfg.process_id >= 0 else None,
        hostfile=cfg.hostfile or None,
    )

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from mpit_tpu.models import TinyDecoder, default_attn, flatten_module
    from mpit_tpu.parallel.mesh import (
        process_local_rows, put_global, put_local,
    )
    from mpit_tpu.parallel.ring_attention import ring_attention
    from mpit_tpu.utils.platform import default_devices

    log = get_logger("lm", pg.process_id)
    if cfg.compile_cache:
        from mpit_tpu.utils.platform import enable_compile_cache

        log.info("compile cache: %s", enable_compile_cache())
    devs = default_devices()
    dp = int(cfg.dp) or 1
    sp = int(cfg.sp) or len(devs) // dp
    if dp * sp != len(devs):
        raise ValueError(f"dp*sp = {dp}*{sp} != {len(devs)} devices")
    mesh = Mesh(np.asarray(devs).reshape(dp, sp), ("dp", "sp"))
    log.info("mesh: dp=%d sp=%d", dp, sp)
    if cfg.batch % dp:
        raise ValueError(f"--batch {cfg.batch} not divisible by dp={dp}")
    if cfg.seq_len % max(sp, 1):
        raise ValueError(f"--seq_len {cfg.seq_len} not divisible by sp={sp}")

    cast = jnp.bfloat16 if cfg.attn_dtype == "bfloat16" else None
    inner = (ring_attention(mesh, "sp", causal=True, batch_axis="dp",
                            layout=cfg.layout)
             if sp > 1 else default_attn(causal=True))

    def attn_fn(q, k, v):
        out_dtype = q.dtype
        if cast is not None:
            q, k, v = (t.astype(cast) for t in (q, k, v))
        return inner(q, k, v).astype(out_dtype)

    model = TinyDecoder(
        vocab=256, d_model=cfg.d_model, n_heads=cfg.n_heads,
        n_layers=cfg.n_layers, max_len=cfg.seq_len, attn_fn=attn_fn,
    )
    # ring_attention(batch_axis="dp") shard_maps the init sample's batch
    # axis over dp, so the sample must be dp-divisible exactly like a
    # training batch — a (batch//dp)-row sample would shard over dp
    # *again* and crash for valid configs (e.g. dp=4 sp=2 batch=8:
    # 2 rows % 4 != 0).  dp rows is the smallest valid sample; param
    # shapes don't depend on batch.
    sample = jnp.zeros((dp, cfg.seq_len), jnp.int32)
    flat = flatten_module(model, jax.random.PRNGKey(cfg.seed), sample)
    log.info("flat params: %d", flat.size)

    batch_sharding = NamedSharding(mesh, P("dp", None))

    def loss_fn(w, toks):
        logp = flat.apply_flat(w, toks[:, :-1])
        tgt = toks[:, 1:]
        return -jnp.mean(jnp.take_along_axis(logp, tgt[..., None], -1))

    # Full Nesterov msgd (the framework's split lookahead/commit halves,
    # optim/msgd.py — same math as the mesh trainers).
    from mpit_tpu.optim.msgd import MSGDConfig, msgd_commit, msgd_lookahead

    mcfg = MSGDConfig(lr=cfg.lr, mom=cfg.mom)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def train_step(w, vt, k, toks):
        st = {"k": k, "vt": vt}
        w_la, st = msgd_lookahead(w, st, mcfg)
        loss, g = jax.value_and_grad(loss_fn)(w_la, toks)
        w2, st2 = msgd_commit(w_la, g, st, mcfg)
        return w2, st2["vt"], k + 1, loss

    # Replicated placement over the global mesh: a multi-host program
    # cannot place host-local arrays on non-addressable devices
    # (put_global docstring, parallel/mesh.py).
    rep = NamedSharding(mesh, P())
    w = put_global(flat.w0, rep)
    vt = put_global(jnp.zeros_like(flat.w0), rep)
    k_step = put_global(jnp.zeros((), jnp.int32), rep)
    start_step = 0
    prev_elapsed = 0.0
    resume_path = cfg.resume
    if resume_path == "auto":
        if not cfg.ckpt_dir:
            raise ValueError("--resume auto requires --ckpt_dir")
        resume_path = str(pathlib.Path(cfg.ckpt_dir) / "lm_latest.npz")
    if resume_path:
        from mpit_tpu.utils.checkpoint import load_state_dict

        saved, meta = load_state_dict(resume_path)
        if saved["w"].shape != tuple(flat.w0.shape):
            raise ValueError(
                f"checkpoint params {saved['w'].shape} != model "
                f"{tuple(flat.w0.shape)} — different --d_model/--n_layers/"
                "--seq_len?"
            )
        want = {"d_model": cfg.d_model, "n_heads": cfg.n_heads,
                "n_layers": cfg.n_layers, "seq_len": cfg.seq_len}
        if "model" in meta and meta["model"] != want:
            raise ValueError(
                f"checkpoint model config {meta['model']} != {want} — "
                "same flat size does not make the same model (n_heads "
                "changes the attention head split silently)"
            )
        if "seed" in meta and int(meta["seed"]) != int(cfg.seed):
            raise ValueError(
                f"checkpoint was trained with --seed {meta['seed']}, "
                f"resuming with --seed {cfg.seed} would silently diverge "
                "the data stream — pass the original seed"
            )
        # The skipped-step burn draws cfg.batch starts per step and the
        # synthetic corpus size depends on batch: a different --batch (or
        # corpus) silently diverges the stream exactly like a seed change.
        if "batch" in meta and int(meta["batch"]) != int(cfg.batch):
            raise ValueError(
                f"checkpoint was trained with --batch {meta['batch']}, "
                f"resuming with --batch {cfg.batch} would silently diverge "
                "the data stream — pass the original batch"
            )
        # meta stores the save-time *resolved* path; resolving the saved
        # string here against the resume-time cwd would compare the wrong
        # file whenever the cwds differ.
        if ("text_file" in meta
                and meta["text_file"] != _corpus_key(cfg.text_file)):
            raise ValueError(
                f"checkpoint was trained on {meta['text_file']!r}, "
                f"resuming on {cfg.text_file!r} is a different corpus"
            )
        w = put_global(jnp.asarray(saved["w"]), rep)
        vt = put_global(jnp.asarray(saved["vt"]), rep)
        k_step = put_global(jnp.asarray(saved["k"]), rep)
        start_step = int(meta.get("step", -1)) + 1
        prev_elapsed = float(meta.get("elapsed", 0.0))
        log.info("resumed at step %d", start_step)

    data = _corpus(cfg, log)
    rng = np.random.default_rng(cfg.seed)
    # Burn the skipped steps' sampling so a resumed run continues the
    # stream (one draw of cfg.batch starts per step).
    for _ in range(start_step):
        rng.integers(0, len(data) - cfg.seq_len - 1, cfg.batch)

    rows = (process_local_rows(batch_sharding, cfg.batch)
            if pg.num_processes > 1 else slice(None))

    # Compile + warm the step program before t0 (mesh_launch's
    # precompile discipline): the jits donate w/vt, so copies run
    # through them and are discarded — tokens_per_sec measures training,
    # not XLA, and compile_s is reported separately.
    t_c = time.perf_counter()
    warm_tokens = put_local(
        jnp.zeros((cfg.batch, cfg.seq_len + 1), jnp.int32)[rows],
        batch_sharding)
    warm_out = train_step(jnp.copy(w), jnp.copy(vt), jnp.copy(k_step),
                          warm_tokens)
    # Host fetch fences the warm execution — without a fence compile_s
    # stops early and the warm step bleeds into the timed region.
    from mpit_tpu.utils.timing import fetch_scalar

    fetch_scalar(warm_out[-1])
    compile_s = time.perf_counter() - t_c
    log.info("precompile: %.2fs", compile_s)

    losses: List = []
    history: List[dict] = []
    t0 = time.perf_counter()
    for step in range(start_step, cfg.steps):
        starts = rng.integers(0, len(data) - cfg.seq_len - 1, cfg.batch)
        toks = np.stack([data[s:s + cfg.seq_len + 1] for s in starts])
        toks = put_local(jnp.asarray(toks[rows], jnp.int32), batch_sharding)
        w, vt, k_step, loss = train_step(w, vt, k_step, toks)
        losses.append(loss)
        if (step + 1) % max(int(cfg.log_every), 1) == 0:
            avg = float(jnp.mean(jnp.stack(losses)))
            losses.clear()
            log.info("step %d loss %.4f (%.1fs)", step, avg,
                     time.perf_counter() - t0 + prev_elapsed)
            history.append({"step": step, "avg_loss": avg})
        if (cfg.ckpt_dir and pg.process_id == 0
                and (step + 1) % max(int(cfg.ckpt_every), 1) == 0):
            from mpit_tpu.utils.checkpoint import save_state_dict

            save_state_dict(
                cfg.ckpt_dir,
                {"w": np.asarray(w), "vt": np.asarray(vt),
                 "k": np.asarray(k_step)},
                meta={"step": step, "seed": cfg.seed,
                      "batch": cfg.batch,
                      "text_file": _corpus_key(cfg.text_file),
                      "model": {"d_model": cfg.d_model,
                                "n_heads": cfg.n_heads,
                                "n_layers": cfg.n_layers,
                                "seq_len": cfg.seq_len},
                      "elapsed": round(time.perf_counter() - t0
                                       + prev_elapsed, 3)},
                prefix="lm",
            )
    elapsed = time.perf_counter() - t0 + prev_elapsed
    if losses:
        history.append({
            "step": cfg.steps - 1,
            "avg_loss": float(jnp.mean(jnp.stack(losses))),
        })
    trained = (cfg.steps - start_step) * cfg.batch * cfg.seq_len
    return {
        "history": history,
        "final_loss": history[-1]["avg_loss"] if history else None,
        "elapsed": round(elapsed, 3),
        "tokens_trained": trained,
        "tokens_per_sec": round(trained / max(elapsed - prev_elapsed, 1e-9), 1),
        "compile_s": round(compile_s, 3),
        "mesh": {"dp": dp, "sp": sp},
        "params": flat.size,
        "processes": pg.num_processes,
    }


def main(argv: Optional[List[str]] = None) -> None:
    cfg = LM_LAUNCH_DEFAULTS.parse_args(
        list(sys.argv[1:] if argv is None else argv)
    )
    print(json.dumps(run(cfg), indent=2))


if __name__ == "__main__":
    main()

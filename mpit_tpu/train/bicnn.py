"""BiCNN trainer — the bicnn.lua workload, TPU-first.

Covers the reference's whole training file (BiCNN/bicnn.lua): the
negative-sampling feval (:305-410), margin ranking loss (:121),
L1/L2 regularization and gradient clamp (:387-409), the loss print every
2000 fevals (:414-418), the test3 evaluation over valid/test1/test2 with
best-accuracy tracking (:465-571), the dedicated-tester pull/eval/save
loop (:580-596), the shuffled train loop with commperiod-gated lastClient
testing (:598-638), and the 12-name optimizer dispatch (:127-252) mapped
onto this framework's optimizer family.

TPU-native feval (the key redesign). The reference scores negatives one
at a time in a data-dependent rejection loop (bicnn.lua:321-359) — a
shape/control-flow pattern XLA cannot compile.  Here each example draws
its ``maxnegsample`` candidate labels up front (host RNG, rejecting gold
labels exactly like the inner ``while`` at :325-330), and ONE jitted
program scores all (B, K) candidates batched, selects per example the
FIRST margin-violating candidate (the reference's early-``break``
semantics, :348-358), and computes loss + grad for the selected pairs.
Examples with no violating candidate among K contribute zero loss and
zero gradient — the ``goto continue`` path (:361-371).  Same sampling
semantics, but the candidate scoring rides the MXU as one batched matmul
instead of up to 100 sequential single-pair forwards.

Deliberate trajectory-level differences (async SGD has no golden
trajectory — SURVEY.md section 7):
- the reference clamps the *accumulated* gradient after every example
  (:398-409); here the batch gradient is clamped once — both end within
  ±grad_clip;
- regularization is added once per contributing example there; here the
  batch term is scaled by the number of contributing examples — same sum.
"""

from __future__ import annotations

import functools
import pathlib
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from mpit_tpu.data.qa import QAData, EvalSet, load_qa
from mpit_tpu.models.bicnn import BiCNN, gesd, margin_ranking_loss
from mpit_tpu.models.flat import FlatModel
from mpit_tpu.optim import EAMSGD, MSGD, Downpour, RuleShell, SingleWorker
from mpit_tpu.optim import rules as rules_mod
from mpit_tpu.optim.msgd import MSGDConfig, committed
from mpit_tpu.utils.checkpoint import load_flat, save_flat
from mpit_tpu.utils.config import Config
from mpit_tpu.utils.logging import get_logger
from mpit_tpu.obs import PhaseTimers

# The full plaunch.lua flag surface (reference BiCNN/plaunch.lua:7-69),
# snake_cased; rebuild-only knobs at the bottom.
QA_FILE_KEYS = ("embedding_file", "train_file", "valid_file",
                "test_file1", "test_file2", "label2answ_file")


def explicit_qa_files(cfg) -> bool:
    """True when ALL six corpus files are given explicitly — the ONE
    predicate deciding whether file flags take precedence over the
    docqa fixture (shared by the trainer's _load_data and the launcher's
    parent-side validation, which must agree)."""
    return all(cfg.get(k, "none") != "none" for k in QA_FILE_KEYS)


BICNN_DEFAULTS = Config(
    optimization="downpour",  # sgd|downpour|eamsgd|adam|adamax|adamsingle|
    #   adamaxsingle|rmsprop|rmspropsingle|adagrad|adagradsingle|adadelta|
    #   adadeltasingle (plaunch.lua:11)
    learning_rate=1e-2,
    batch_size=1,  # plaunch.lua:13 (1 = pure stochastic)
    lr_adagrad=1e-3,
    lr_decay_adagrad=1e-6,
    epsilon_adagrad=1e-10,
    rho_adadelta=0.9,
    lr_adadelta=1.0,
    epsilon_adadelta=1e-6,
    lr_adam=1e-3,
    beta1_adam=0.9,
    beta2_adam=0.999,
    epsilon_adam=1e-8,
    step_div_adam=72,
    grad_clip=0.5,
    weight_decay=1e-6,
    decay_rmsprop=0.95,
    lr_rmsprop=1e-4,
    momentum_rmsprop=0.9,
    epsilon_rmsprop=1e-4,
    momentum=0.0,
    commperiod=1,
    movingrate=0.05,
    dtype="float32",  # the 'type' flag: double|float|cuda -> array dtype
    train_file="none",
    valid_file="none",
    test_file1="none",
    test_file2="none",
    label2answ_file="none",
    embedding_file="none",
    embedding_dim=100,
    cont_conv_width=2,
    word_hidden_dim=200,
    num_filters=3000,
    epoch=50,
    l1reg=0.0,
    l2reg=1e-4,
    margin=0.02,
    maxnegsample=100,
    valid_mode="additionalTester",  # none | lastClient | additionalTester
    valid_sleep_time=1.0,
    mmode=1,  # 1|2 — graph-plumbing variants of the same math (models/bicnn.py)
    outputprefix="none",
    prevtime=0.0,
    loadmodel="none",
    preload_binary=False,
    binary_path="",  # where the preload_binary cache lives (.npz)
    testerfirst=False,
    testerlast=False,
    master_freq=2,
    maxrank=120,
    singlemode=False,
    docqa=False,  # train on the committed real stdlib-docstring corpus
    #   (data/fixtures/docqa; wins over synthetic when no --*_file given)
    # -- rebuild-only ------------------------------------------------------
    seed=1,
    loss_report_every=2000,  # bicnn.lua:414 prints every 2000 fevals
    tester_rounds=10,  # bounded tester lifecycle (the reference's never
    #   stops — flagged TODO at bicnn.lua:581)
    eval_chunk=64,  # batch size for answer/query embedding at eval
)

_SINGLE = {
    "adamsingle": "adam", "adamaxsingle": "adamax", "rmspropsingle": "rmsprop",
    "adagradsingle": "adagrad", "adadeltasingle": "adadelta",
}
_GLOBAL = ("adam", "adamax", "rmsprop", "adagrad", "adadelta")


def rule_hyperparams(cfg: Config, rule: str) -> Dict[str, Any]:
    """Per-method hyperparameters from the plaunch flag groups
    (reference plaunch.lua:15-36 -> pserver dispatch BiCNN/pserver.lua:123-197)."""
    if rule == "adam":
        return dict(lr=cfg.lr_adam, beta1=cfg.beta1_adam,
                    beta2=cfg.beta2_adam, epsilon=cfg.epsilon_adam)
    if rule == "adamax":
        return dict(lr=cfg.lr_adam, beta1=cfg.beta1_adam,
                    beta2=cfg.beta2_adam, epsilon=cfg.epsilon_adam)
    if rule == "rmsprop":
        return dict(lr=cfg.lr_rmsprop, decay=cfg.decay_rmsprop,
                    momentum=cfg.momentum_rmsprop, epsilon=cfg.epsilon_rmsprop)
    if rule == "adagrad":
        return dict(lr=cfg.lr_adagrad, lrd=cfg.lr_decay_adagrad,
                    epsilon=cfg.epsilon_adagrad)
    if rule == "adadelta":
        return dict(lr=cfg.lr_adadelta, rho=cfg.rho_adadelta,
                    epsilon=cfg.epsilon_adadelta)
    raise ValueError(f"no hyperparameter group for rule {rule!r}")


def server_rule_for(cfg: Config):
    """Server-side shard rule matching the client optimizer — the BiCNN
    pserver's conf.opt dispatch (reference BiCNN/pserver.lua:123-197)."""
    name = cfg.optimization
    if name in _GLOBAL:
        hp = rule_hyperparams(cfg, name)
        if name == "adam":
            # Adam's server-side bias correction is stepDiv-scaled
            # (reference BiCNN/pserver.lua:140-155).
            hp["step_div"] = cfg.step_div_adam
        return rules_mod.make(name, **hp)
    return rules_mod.make("add")


def gesd_np(q: np.ndarray, a: np.ndarray) -> np.ndarray:
    """Host-side GESD over (F,) x (P, F) — the eval-time inlined formula
    (reference bicnn.lua:440-443).  Kept as the semantic oracle for the
    device scorer (:func:`_pool_score`); tests compare the two."""
    dot = a @ q
    l2 = np.sqrt(np.maximum(((a - q) ** 2).sum(axis=-1), 0.0))
    return 1.0 / ((1.0 + l2) * (1.0 + np.exp(-(dot + 1.0))))


def _pool_score(q_emb, ans_emb, idx, mask, hit):
    """Device-side pool-restricted selection: correct count over all
    questions in one XLA program (replaces the reference's per-question
    host loop, bicnn.lua:426-460 — quadratic host pain at real pool
    sizes).

    Each question's padded candidate pool is gathered from the answer
    matrix and scored with the *direct* GESD form — same arithmetic as
    the host oracle :func:`gesd_np` (an expanded |q|^2+|a|^2-2qa form
    would catastrophically cancel exactly for the near-ties that decide
    argmax).  ``lax.map`` over question chunks bounds memory at
    O(chunk * P * F) regardless of question count.  ``idx/mask`` encode
    the pools (mask: candidate known to the answer space, bicnn.lua:434
    filter), ``hit`` whether a slot's label is gold.  Ties keep the
    LAST maximum (reference bicnn.lua:444-447), via argmax of the
    reversed pool axis."""
    chunk = 32
    qf = q_emb.astype(jnp.float32)
    af = ans_emb.astype(jnp.float32)
    n, p = idx.shape
    pad = (-n) % chunk
    if pad:
        qf = jnp.pad(qf, ((0, pad), (0, 0)))
        idx = jnp.pad(idx, ((0, pad), (0, 0)))
        mask = jnp.pad(mask, ((0, pad), (0, 0)))  # False: never counted
        hit = jnp.pad(hit, ((0, pad), (0, 0)))

    def score_chunk(args):
        qc, ic, mc, hc = args  # (C, F), (C, P), (C, P), (C, P)
        ac = af[ic]  # (C, P, F)
        dot = jnp.einsum("cpf,cf->cp", ac, qc)
        l2 = jnp.sqrt(jnp.maximum(
            jnp.sum((ac - qc[:, None, :]) ** 2, axis=-1), 0.0))
        sims = 1.0 / ((1.0 + l2) * (1.0 + jnp.exp(-(dot + 1.0))))
        sims = jnp.where(mc, sims, -jnp.inf)
        best = p - 1 - jnp.argmax(sims[:, ::-1], axis=1)  # LAST max
        chosen_hit = jnp.take_along_axis(hc, best[:, None], axis=1)[:, 0]
        return jnp.sum((chosen_hit & jnp.any(mc, axis=1)).astype(jnp.int32))

    counts = jax.lax.map(score_chunk, (
        qf.reshape(-1, chunk, qf.shape[1]),
        idx.reshape(-1, chunk, p),
        mask.reshape(-1, chunk, p),
        hit.reshape(-1, chunk, p),
    ))
    return jnp.sum(counts)


class BiCNNTrainer:
    """The bicnn.lua workload driver (train or tester role)."""

    def __init__(
        self,
        cfg: Optional[Config] = None,
        pclient: Any = None,
        data: Optional[QAData] = None,
        rank: int = 0,
    ):
        self.cfg = cfg = BICNN_DEFAULTS.merged(cfg.to_dict() if cfg else None)
        self.pc = pclient
        self.rank = rank
        self.log = get_logger("bicnn", rank)
        self.tm = PhaseTimers()
        self.rng = np.random.default_rng(cfg.seed + rank)

        if data is None:
            data = self._load_data()
        self.data = data
        self.log.info(
            "data: %s (%d train, %d answers, vocab %d)",
            data.source, len(data.train), data.answer_space, len(data.vocab),
        )

        vocab_matrix = data.vocab.matrix()
        # Pretrained-vector initialization of the lookup table
        # (reference bicnn.lua:34).
        def embedding_init(key, shape, dtype=jnp.float32):
            assert tuple(shape) == vocab_matrix.shape, (shape, vocab_matrix.shape)
            return jnp.asarray(vocab_matrix, dtype)

        self.module = BiCNN(
            vocab_size=len(data.vocab),
            # the data's embedding width is authoritative — a corpus
            # loaded from files (e.g. the 50-dim docqa fixture) wins
            # over the config default
            embedding_dim=data.vocab.embedding_dim,
            word_hidden_dim=cfg.word_hidden_dim,
            num_filters=cfg.num_filters,
            conv_width=cfg.cont_conv_width,
            embedding_init=embedding_init,
        )
        rng_key = jax.random.PRNGKey(cfg.seed)
        sample_tok = jnp.asarray(data.train.q_tokens[:1])
        sample_len = jnp.asarray(data.train.q_len[:1])
        params = self.module.init(
            rng_key, sample_tok, sample_len, sample_tok, sample_len,
            sample_tok, sample_len,
        )["params"]
        self.flat = FlatModel(self.module, params)
        self.w = self.flat.w0.astype(jnp.dtype(cfg.dtype))
        if cfg.loadmodel != "none":
            w, meta = load_flat(cfg.loadmodel)
            self.w = jnp.asarray(w, self.w.dtype)  # bicnn.lua:259-261
            self.log.info("resumed from %s (meta %s)", cfg.loadmodel, meta)

        self._embed = jax.jit(
            lambda w, t, l: self.flat.module.apply(
                {"params": self.flat.unravel(w)}, t, l, method=BiCNN.embed
            )
        )
        self._pool_cache: Dict[str, tuple] = {}
        self._pool_score = jax.jit(_pool_score)
        self._vgf = self._build_vgf()
        # loss-print accumulators (bicnn.lua:283, :414-418).  A running
        # *device* scalar sum, fetched only at report time — a float()
        # per step would fence the dispatch pipeline on every batch, and
        # a list of per-step scalars would grow without bound when
        # reporting is disabled.
        self._loss_acc: Any = None
        self._loss_count = 0
        self.best = {}  # per-dataset best accuracy/epoch (bicnn.lua:505-571)
        self.epoch = 0

    # -- data ----------------------------------------------------------------

    def _load_data(self) -> QAData:
        cfg = self.cfg
        explicit_files = explicit_qa_files(cfg)
        # Effective embedding width, resolved ONCE so every branch
        # (binary cache validation included) agrees: docqa's 50-dim
        # files override an untouched 100-dim config default — but only
        # when the docqa branch would actually load the data (explicit
        # --*_file flags take precedence over the fixture).
        want_dim = cfg.embedding_dim
        if (cfg.get("docqa", False) and not explicit_files
                and cfg.embedding_dim == BICNN_DEFAULTS.embedding_dim):
            from mpit_tpu.data.qa import DOCQA_EMBEDDING_DIM

            want_dim = DOCQA_EMBEDDING_DIM
        cache = pathlib.Path(cfg.binary_path) if (
            cfg.preload_binary and cfg.binary_path
        ) else None
        if cache is not None and cache.exists():
            return load_qa(
                binary_path=cache,
                conv_width=cfg.cont_conv_width,
                embedding_dim=want_dim,
            )
        if explicit_files:
            data = load_qa(
                embedding_dim=cfg.embedding_dim,
                conv_width=cfg.cont_conv_width,
                paths={k: pathlib.Path(cfg.get(k)) for k in QA_FILE_KEYS},
                oov_seed=cfg.seed,
            )
        elif cfg.get("docqa", False):
            # The committed REAL corpus (stdlib docstrings).
            from mpit_tpu.data.qa import docqa_paths

            paths = docqa_paths()
            if paths is None:
                raise FileNotFoundError(
                    "docqa=1 but data/fixtures/docqa is absent — run "
                    "tools/make_docqa.py or use explicit --*_file flags"
                )
            data = load_qa(
                embedding_dim=want_dim, conv_width=cfg.cont_conv_width,
                paths=paths, oov_seed=cfg.seed,
            )
            data.source = "docqa fixture (real stdlib-docstring corpus)"
        else:
            data = load_qa(
                embedding_dim=cfg.embedding_dim,
                conv_width=cfg.cont_conv_width,
                oov_seed=cfg.seed,
            )
        if cache is not None:
            # First run with preload_binary populates the cache — the
            # analog of generating the reference's checked-in binaries
            # (plaunch.lua:218-229).
            from mpit_tpu.data.qa import save_binary

            save_binary(data, cache)
            self.log.info("wrote binary cache %s (from %s)", cache, data.source)
        return data

    # -- feval ---------------------------------------------------------------

    def _build_vgf(self):
        cfg = self.cfg
        margin = float(cfg.margin)
        l1, l2 = float(cfg.l1reg), float(cfg.l2reg)
        clip = float(cfg.grad_clip)
        apply_flat = self.flat.apply_flat

        def loss_fn(w, q, ql, ap, apl, nt, nl):
            b, k, la = nt.shape
            # One tower pass per distinct input — tying by construction.
            eq = apply_flat(w, q, ql, method=BiCNN.embed)  # (B, F)
            ep = apply_flat(w, ap, apl, method=BiCNN.embed)  # (B, F)
            en = apply_flat(
                w, nt.reshape(b * k, la), nl.reshape(b * k), method=BiCNN.embed
            ).reshape(b, k, -1)  # batched candidate towers, (B, K, F)
            s_pos = gesd(eq, ep)  # (B,)
            en_scores = gesd(eq[:, None, :], en)  # (B, K)
            # First margin-violating candidate per example — the
            # sequential-break semantics (bicnn.lua:348-358).
            viol = (s_pos[:, None] - en_scores) < margin
            has = jnp.any(viol, axis=1)
            first = jnp.argmax(viol, axis=1)
            onehot = jax.nn.one_hot(first, k, dtype=en_scores.dtype)
            s_neg = jnp.sum(onehot * en_scores, axis=1)
            per_ex = margin_ranking_loss(s_pos, s_neg, margin) * has
            n_contrib = jnp.sum(has.astype(w.dtype))
            f = jnp.sum(per_ex)
            # Per-contributing-example regularization (bicnn.lua:387-397).
            if l1:
                f = f + n_contrib * l1 * jnp.sum(jnp.abs(w))
            if l2:
                f = f + n_contrib * l2 * 0.5 * jnp.sum(w * w)
            return f

        raw = jax.value_and_grad(loss_fn)

        def vgf(w, *args):
            loss, g = raw(w, *args)
            return loss, jnp.clip(g, -clip, clip)  # bicnn.lua:398-409

        return vgf

    def sample_negatives(self, batch_labels: List[List[int]]) -> Tuple[np.ndarray, np.ndarray]:
        """Draw (B, K) candidate answer rows, rejecting gold labels — the
        host half of the rejection loop (bicnn.lua:325-330)."""
        data, k = self.data, int(self.cfg.maxnegsample)
        a = data.answer_space
        rows = self.rng.integers(0, a, size=(len(batch_labels), k))
        l2r = data.label2row
        for i, gold in enumerate(batch_labels):
            gold_rows = {l2r[g] for g in gold if g in l2r}
            if not gold_rows or len(gold_rows) >= a:
                continue
            bad = np.isin(rows[i], list(gold_rows))
            while bad.any():
                rows[i, bad] = self.rng.integers(0, a, size=int(bad.sum()))
                bad = np.isin(rows[i], list(gold_rows))
        nt = data.answer_tokens[rows]  # (B, K, La)
        nl = data.answer_len[rows]  # (B, K)
        return nt.astype(np.int32), nl.astype(np.int32)

    # -- optimizer dispatch (bicnn.lua:127-252, plaunch names) ---------------

    KNOWN_OPTS = ("sgd", "downpour", "eamsgd", "easgd") + _GLOBAL + tuple(_SINGLE)

    @functools.cached_property
    def optimizer(self):
        """Built at first use: eval-only roles never need one."""
        cfg = self.cfg
        name = cfg.optimization
        if name not in self.KNOWN_OPTS:
            raise ValueError(f"unknown optimization {name!r}; have {self.KNOWN_OPTS}")
        if name == "sgd":
            return MSGD(
                MSGDConfig(lr=cfg.learning_rate, mom=cfg.momentum,
                           l2wd=cfg.weight_decay),
                self._vgf,
            )
        if self.pc is None:
            raise ValueError(f"optimization {name!r} needs a parameter client")
        if name == "downpour":
            return Downpour(self._vgf, self.pc, lr=cfg.learning_rate,
                            su=cfg.commperiod)
        if name in ("eamsgd", "easgd"):
            mom = 0.0 if name == "easgd" else cfg.momentum
            return EAMSGD(self._vgf, self.pc, lr=cfg.learning_rate, mom=mom,
                          mva=cfg.movingrate, su=cfg.commperiod)
        if name in _GLOBAL:
            # Accumulate-and-ship; the server applies the stateful rule
            # (reference BiCNN/optim-adam.lua etc. + pserver dispatch).
            return RuleShell(self._vgf, self.pc, su=cfg.commperiod, mode="global")
        rule = _SINGLE[name]
        return SingleWorker(self._vgf, self.pc, rule=rule,
                            **rule_hyperparams(cfg, rule))

    # -- evaluation (test3, bicnn.lua:465-571) -------------------------------

    def _embed_chunked(self, w, tokens: np.ndarray, lengths: np.ndarray) -> jnp.ndarray:
        """Embed (N, L) in fixed-size chunks (static shapes; one compile).
        Returns a device array — the scorer consumes it in place, so
        eval never round-trips embeddings through the host."""
        chunk = int(self.cfg.eval_chunk)
        n = tokens.shape[0]
        pad = (-n) % chunk
        if pad:
            tokens = np.concatenate([tokens, np.repeat(tokens[:1], pad, 0)])
            lengths = np.concatenate([lengths, np.repeat(lengths[:1], pad)])
        outs = [
            self._embed(w, jnp.asarray(tokens[i : i + chunk]),
                        jnp.asarray(lengths[i : i + chunk]))
            for i in range(0, tokens.shape[0], chunk)
        ]
        return jnp.concatenate(outs)[:n]

    def _pool_tables(self, eval_set: EvalSet, name: str):
        """Padded device tables for one eval set, built once and cached
        (pools and labels never change during a run): ``idx`` (N, P)
        answer-matrix rows, ``mask`` slot validity (candidate known to
        the answer space, bicnn.lua:434 filter), ``hit`` whether the
        slot's label is gold for its question."""
        cached = self._pool_cache.get(name)
        if cached is not None and cached[0] is eval_set:
            return cached[1:]
        l2r = self.data.label2row
        n = len(eval_set)
        p = max((len(pool) for pool in eval_set.pools), default=1) or 1
        idx = np.zeros((n, p), np.int32)
        mask = np.zeros((n, p), bool)
        hit = np.zeros((n, p), bool)
        for i, pool in enumerate(eval_set.pools):
            gold = set(eval_set.labels[i])
            for j, v in enumerate(pool):
                row = l2r.get(v)
                if row is None:
                    continue
                idx[i, j] = row
                mask[i, j] = True
                hit[i, j] = v in gold
        tables = (jnp.asarray(idx), jnp.asarray(mask), jnp.asarray(hit))
        self._pool_cache[name] = (eval_set,) + tables
        return tables

    # the vector to evaluate or save (``self.w`` is the optimizer's to
    # hand back)
    params = property(committed)

    def evaluate(
        self, eval_set: EvalSet, name: str, w=None, ans_emb: Optional[np.ndarray] = None
    ) -> float:
        """Pool-restricted answer selection accuracy for one dataset —
        one leg of test3 (bicnn.lua:465-510).  ``ans_emb`` lets test3
        embed the answer space once for all three datasets."""
        w = self.params if w is None else w
        data = self.data
        with self.tm.phase("test"):
            if ans_emb is None:
                ans_emb = self._embed_chunked(w, data.answer_tokens, data.answer_len)
            q_emb = self._embed_chunked(w, eval_set.q_tokens, eval_set.q_len)
            idx, mask, hit = self._pool_tables(eval_set, name)
            correct = int(self._pool_score(q_emb, ans_emb, idx, mask, hit))
            acc = correct / max(len(eval_set), 1)
        prev = self.best.get(name, (0.0, -1))
        if acc > prev[0]:
            self.best[name] = (acc, self.epoch)
        best_acc = self.best.get(name, (acc, self.epoch))[0]
        self.log.info(
            "curr time: %.2f, Accuracy: %.4f, best Accuracy: %.4f on %s",
            self.tm.elapsed() + float(self.cfg.prevtime), acc, best_acc, name,
        )
        return acc

    def test3(self, w=None) -> Dict[str, float]:
        """Evaluate valid + test1 + test2 (bicnn.lua:465-571, :589).
        The answer space is embedded once and shared across the three
        datasets (the reference re-embeds it per dataset, :467-470)."""
        w_eval = self.params if w is None else w
        with self.tm.phase("test"):
            ans_emb = self._embed_chunked(
                w_eval, self.data.answer_tokens, self.data.answer_len
            )
        return {
            "valid": self.evaluate(self.data.valid, "valid", w_eval, ans_emb),
            "test1": self.evaluate(self.data.test1, "test1", w_eval, ans_emb),
            "test2": self.evaluate(self.data.test2, "test2", w_eval, ans_emb),
        }

    def _save_checkpoint(self) -> None:
        """Runtime-stamped whole-param save (bicnn.lua:590-594)."""
        prefix = self.cfg.outputprefix
        if prefix == "none" or not prefix:
            return
        path = pathlib.Path(prefix)
        runtime = self.tm.elapsed() + float(self.cfg.prevtime)
        save_flat(
            path.parent if path.parent != pathlib.Path("") else pathlib.Path("."),
            self.params,
            {"runtime": runtime, "epoch": self.epoch, "best": dict(self.best)},
            prefix=path.name,
        )

    # -- the train loop (bicnn.lua:598-638) ----------------------------------

    def _batches(self, order: np.ndarray):
        """Static-shape batch assembly: the trailing partial batch wraps
        around the shuffled order (the reference's variable last batch,
        bicnn.lua:612-623, would force an XLA recompile per shape)."""
        b = int(self.cfg.batch_size)
        n = len(order)
        for lo in range(0, n, b):
            idx = order[lo : lo + b]
            if len(idx) < b:
                idx = np.concatenate([idx, order[: b - len(idx)]])
            yield idx

    def step(self, idx: np.ndarray) -> jnp.ndarray:
        """One feval + optimizer step on the batch rows ``idx``.  Returns
        the loss as a device scalar — fetched lazily (report window,
        epoch average) so the dispatch pipeline is never fenced
        per-batch."""
        tr = self.data.train
        labels = [tr.labels[i] for i in idx]
        with self.tm.phase("sample"):
            nt, nl = self.sample_negatives(labels)
        q, ql = jnp.asarray(tr.q_tokens[idx]), jnp.asarray(tr.q_len[idx])
        ap, apl = jnp.asarray(tr.a_tokens[idx]), jnp.asarray(tr.a_len[idx])
        with self.tm.phase("feval"):
            self.w, loss = self.optimizer.step(
                self.w, q, ql, ap, apl, jnp.asarray(nt), jnp.asarray(nl)
            )
        self._loss_acc = loss if self._loss_acc is None else self._loss_acc + loss
        self._loss_count += 1
        if self._loss_count % int(self.cfg.loss_report_every) == 0:
            # One fetch for the whole window.
            self.log.info(
                "curr time: %.2f, training loss avg. : %.5f",
                self.tm.elapsed() + float(self.cfg.prevtime),
                float(self._loss_acc) / self._loss_count,
            )
            self._loss_acc, self._loss_count = None, 0
        return loss

    def run(self, is_last_client: bool = False) -> Dict[str, Any]:
        """Train for cfg.epoch epochs (the non-tester branch,
        bicnn.lua:598-638)."""
        cfg = self.cfg
        opt = self.optimizer
        if hasattr(opt, "start"):
            with self.tm.phase("start"):
                self.w = opt.start(self.w)
        n = len(self.data.train)
        pversion = 0
        history = []
        for epoch in range(int(cfg.epoch)):
            self.epoch = epoch
            t_epoch = time.monotonic()
            order = self.rng.permutation(n)  # shuffle (bicnn.lua:609)
            loss_sum, steps = None, 0
            for idx in self._batches(order):
                loss = self.step(idx)
                loss_sum = loss if loss_sum is None else loss_sum + loss
                steps += 1
                # lastClient in-train testing every commperiod steps
                # (bicnn.lua:625-633).
                if (
                    cfg.valid_mode == "lastClient"
                    and is_last_client
                    and pversion % int(cfg.commperiod) == 0
                ):
                    self.test3()
                    self._save_checkpoint()
                pversion += 1
            history.append({
                "epoch": epoch,
                # One fetch per epoch (not one per step).
                "avg_loss": float(loss_sum) / steps if steps else 0.0,
                "seconds": time.monotonic() - t_epoch,
            })
            self.log.info(
                "epoch %d done, for %.2f seconds", epoch, history[-1]["seconds"]
            )
        accs = self.test3()
        sync = getattr(opt, "sync_seconds", 0.0)
        self.tm.add("sync", sync)
        if hasattr(opt, "stop"):
            with self.tm.phase("stop"):
                opt.stop()
        return {
            "history": history,
            "accuracy": accs,
            "best": {k: {"acc": v[0], "epoch": v[1]} for k, v in self.best.items()},
            "elapsed": self.tm.elapsed(),
            "timers": dict(self.tm.total),
        }

    # -- tester role (additionalTester, bicnn.lua:580-596) -------------------

    def run_tester(self) -> Dict[str, Any]:
        """Pull params -> test3 -> checkpoint -> sleep, for a bounded
        number of rounds (the reference loops forever — TODO at
        bicnn.lua:581; a bounded lifecycle keeps the stop protocol exact)."""
        cfg = self.cfg
        if self.pc is None:
            raise ValueError("tester role needs a parameter client")
        # The tester's freshly-built model params back the client buffers —
        # with testerfirst the tester IS cranks[1] and seeds the servers'
        # initial params from them (reference bicnn.lua:268-271,
        # pclient.lua:125-128).
        param = np.array(self.w, np.dtype(cfg.dtype))
        grad = np.zeros_like(param)
        self.pc.start(param, grad)
        rounds = int(cfg.tester_rounds)
        history = []
        for r in range(rounds):
            self.epoch = r
            t0 = time.monotonic()
            self.pc.async_recv_param()
            self.pc.wait()
            self.log.info("communication time: %.2f", time.monotonic() - t0)
            self.w = jnp.asarray(param)
            accs = self.test3()
            history.append({"round": r, **accs})
            self._save_checkpoint()
            if r != rounds - 1:
                time.sleep(float(cfg.valid_sleep_time))
        self.pc.stop()
        return {
            "history": history,
            "best": {k: {"acc": v[0], "epoch": v[1]} for k, v in self.best.items()},
        }

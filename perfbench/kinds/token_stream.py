"""The kind ``token_stream``: a language model trained online on rows of token
ids through the dense fused, overlapped file route of a ``StreamJob``
(``run_file_fused``: the C line loop, ``SPMDBridge.ingest_file``, one launch a
``batchSize`` rows), compared with a plain reference on its losses, its
parameters and its forecasts.

A record is a JSON line whose numeric features are ``tokens_per_row`` token
ids; a training record's target is the id after the last, a forecast is
answered with the most likely next id. The dense route's predictions carry
the features and no id, so a forecast record's first token is its forecast's
id (ids are smaller than the vocabulary).

What a configuration's file of this kind holds (``configs/<name>.json``):
``kind`` (``token_stream``), ``source``, ``deployment``, ``reference`` (the
module under ``reference/``), the architecture's keys AT THE TOP LEVEL as the
published ``config.json`` names them (``ARCH_KEYS`` below, ``layer_types``
whole, ``num_hidden_layers`` the layers that are run: the first that many of
``layer_types``), ``job_flags`` (``batchSize`` 1: the reference follows
launches of one row), ``create`` (the Create request without its
``dataStructure``, which :func:`create_request` fills from the top-level keys,
the cell's ``tokens_per_row`` and ``--seed``), ``precision``, ``guarantees``,
``rows`` (the pool a closed loop replays), ``reduced``, ``published`` and
``assumed`` (``zipf_exponent``, ``bigram_share``, ``bigram_rule_seed``: token
ids follow a bounded Zipf law over the vocabulary and, with probability
``bigram_share``, a token is the fixed successor of the one before it). A
cell's ``traffic`` adds ``tokens_per_row``; its ``comparison.margin_floor`` is
the reference's top-two logit margin under which a forecast is not judged.

Compared (``limits`` in the cell's file): rows conserved and every forecast
answered once over the whole run; and from the probe, which went through the
window's own call on the window's own job: the probe's forecasts (arg-max,
where the reference's margin exceeds the floor), the loss of every probe step,
the norm of the update after the first probe file, the parameters after the
last (the difference's norm over the norm of the reference's own change from
the initial weights, which a state left unchanged reads as 1: once over the
whole model and once leaf by leaf, the worst leaf counting, so that a small
leaf left unmoved or moved by a wrong gradient reads 1 beside matrices that
are right). The reference starts from the program's own initial weights,
copied to the host when the job is built; those are held against the
reference's OWN seeded initial weights, another draw of the same laws
(:func:`initial_gaps`: shapes, each leaf's mean and spread, the stated ranges,
no two leaves alike).

The readers find ``tokens_per_row``, ``flops`` (``kernel_models/olmo_hybrid``)
and ``scope_of`` (the compiled launch's operation-to-scope table, traced runs
only) through ``ctx``.
"""

from __future__ import annotations

import copy
import json
import os
import re
import threading
import time
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import numpy as np

from perfbench import generator as gen
from perfbench import harness

ARCH_KEYS = ("vocab_size", "hidden_size", "intermediate_size", "num_attention_heads",
             "linear_num_key_heads", "linear_num_value_heads", "linear_key_head_dim",
             "linear_value_head_dim", "linear_conv_kernel_dim", "linear_allow_neg_eigval",
             "rms_norm_eps")

# the configuration at a size a CPU test holds: the published ratios (keys
# half as wide as values, three linear layers to one full), rows that are no
# multiple of the delta rule's chunk. The step size is cut with the widths:
# nearly every matrix feeds a norm, so the loss's curvature along it goes as
# 1 / |W|^2, and a 32 x 32 matrix at the published step would train in a
# regime where one rounding grows threefold a step (what is compared would
# be chaos, not arithmetic)
TINY = {
    "learning_rate": 5e-4,
    "arch": {"vocab_size": 96, "hidden_size": 32, "intermediate_size": 80, "num_attention_heads": 2,
             "linear_num_key_heads": 2, "linear_num_value_heads": 2, "linear_key_head_dim": 8,
             "linear_value_head_dim": 16},
    "rows": 8,
    "traffic": {"tokens_per_row": 150, "part_rows": 4},
}

# the control (parameters and recurrent state in the precision below the
# float32 the configuration states) and the faults a cell of this kind can have
STAND_INS = [("bfloat16", None), ("float32", "chunk_reset"), ("float32", "no_alpha"),
             ("float32", "beta_not_doubled"), ("float32", "conv_shift"),
             ("float32", "skip_full"), ("float32", "half_loss")]


def scaled(config: dict, scale: dict) -> dict:
    config = copy.deepcopy(config)
    config.update(scale["arch"])
    config["rows"] = scale["rows"]
    config["create"]["learner"]["hyperParameters"]["learningRate"] = scale["learning_rate"]
    return config


def create_request(config: dict, tokens_per_row: int, seed: int) -> dict:
    """The Create request of one run: the file's, with the learner's
    ``dataStructure`` filled from the architecture's top-level keys."""
    create = copy.deepcopy(config["create"])
    ds = {k: config[k] for k in ARCH_KEYS}
    ds["layer_types"] = list(config["layer_types"][: int(config["num_hidden_layers"])])
    ds["nFeatures"] = int(tokens_per_row)
    create["learner"]["dataStructure"] = ds
    create["learner"]["hyperParameters"]["seed"] = int(seed) % (2**31 - 1)
    return create


# --- records -----------------------------------------------------------------


@dataclass
class Rows:
    tokens: np.ndarray  # [n, L] int32
    target: np.ndarray  # [n] int32


def _zipf_ids(rng, shape, vocab: int, s: float) -> np.ndarray:
    """Ids in [0, vocab) from a bounded Zipf law (inverse of the continuous
    distribution function); id 0 is the most frequent."""
    u = rng.random(shape)
    a = 1.0 - s
    r = (u * ((vocab + 1.0) ** a - 1.0) + 1.0) ** (1.0 / a)
    return np.clip(np.floor(r).astype(np.int64), 1, vocab) - 1


def draw_rows(rng, n: int, length: int, vocab: int, assumed: dict) -> Rows:
    successor = np.random.default_rng(int(assumed["bigram_rule_seed"])).integers(0, vocab, vocab)
    fresh = _zipf_ids(rng, (n, length + 1), vocab, float(assumed["zipf_exponent"]))
    follows = rng.random((n, length + 1)) < float(assumed["bigram_share"])
    ids = fresh.copy()
    for t in range(1, length + 1):
        ids[:, t] = np.where(follows[:, t], successor[ids[:, t - 1]], fresh[:, t])
    return Rows(ids[:, :length].astype(np.int32), ids[:, length].astype(np.int32))


def render(rows: Rows, forecast: bool) -> gen.Rendered:
    lines = []
    for tokens, target in zip(rows.tokens.tolist(), rows.target.tolist()):
        feats = ", ".join(map(str, tokens))
        lines.append(('{"numericalFeatures": [%s], "operation": "forecasting"}\n' % feats) if forecast else
                     ('{"numericalFeatures": [%s], "target": %d, "operation": "training"}\n' % (feats, target)))
    blob = "".join(lines).encode()
    offsets = np.zeros(len(lines) + 1, np.int64)
    np.cumsum([len(l) for l in lines], out=offsets[1:])
    return gen.Rendered(np.frombuffer(blob, np.uint8), offsets)


class Pool:
    """The seeded pool of training rows a closed loop replays, rendered once."""

    def __init__(self, lines: gen.Rendered):
        self.lines = lines

    def spans(self, a: int, b: int) -> List[memoryview]:
        return [self.lines.span(a, b)]


# --- the system under test ---------------------------------------------------


class System:
    """The job as ``python -m omldm_tpu`` builds it from the configuration's
    flags, its Create request through the normal entry."""

    def __init__(self, config: dict, create: dict, on_prediction):
        import jax

        from omldm_tpu.__main__ import build_job as cli_build_job

        job, _sinks = cli_build_job(dict(config["job_flags"]))
        job.set_sinks(on_prediction=on_prediction, on_response=lambda r: None,
                      on_performance=lambda r: None)
        job.process_event("requests", json.dumps(create))
        job.ensure_deployed(int(create["learner"]["dataStructure"]["nFeatures"]))
        bridge = job.fused_file_bridge()
        if bridge is None or not bridge.supports_overlapped_ingest() or hasattr(bridge, "_launch_coo"):
            raise RuntimeError("the job does not take the dense fused, overlapped file route")
        self.job, self.bridge = job, bridge
        self._marker = jax.jit(lambda s: s + 0)

    def hand_over(self, path: str) -> None:
        self.job.run_file_fused(path)

    def marker(self):
        return self._marker(self.bridge.trainer.state["step"])

    def wait(self) -> None:
        import jax

        jax.block_until_ready(self.bridge.trainer.state)

    def host_params(self) -> dict:
        """Worker 0's parameters, copied to the host leaf by leaf."""
        import jax

        trainer = self.bridge.trainer
        return jax.tree_util.tree_map(
            lambda leaf: np.asarray(trainer.shard0(jax.device_get(leaf))), trainer.state["params"])

    def close(self) -> Dict[str, int]:
        self.job.terminate()
        return {"fitted": int(self.bridge.trainer.fitted), "holdout": len(self.bridge.test_set)}


# --- the kind ----------------------------------------------------------------


@dataclass
class Readings:
    """What is compared: of the program, or of a reference in its place."""

    losses: List[float]
    first_update_norm: float
    initial: dict  # leaf tree, host
    final: dict
    answers: List[tuple]  # (forecast id, token[, margin])


def _leaves(tree) -> List[np.ndarray]:
    import jax

    return jax.tree_util.tree_leaves(tree)


def _sumsq(a: np.ndarray) -> float:
    return float(np.sum(np.square(a, dtype=np.float64)))


def distance(a: dict, b: dict) -> float:
    return float(np.sqrt(sum(_sumsq(x.astype(np.float32) - y) for x, y in zip(_leaves(a), _leaves(b)))))


# a leaf is judged alone where the reference moved it by this share of its
# norm or more: 50 steps of float32's grid (2^-24 of an element) over the
# probe's eight updates. Under it the ratio reads how two float32 programs
# cancel and round, not which gradient they follow: at the test's size, where
# the step is a twentieth of the published one, a sound run reads up to 0.40
# on a two-element leaf moved by 1.6e-6 and at most 0.09 from 3e-6 on. At the
# cell's size only the gains on q and k (1.2e-6) lie under it: PERF.md section 2
LEAF_CHANGE_FLOOR = 3e-6


def leaf_update_gaps(got: dict, want: dict, initial: dict) -> Dict[str, Tuple[float, float]]:
    """``{leaf: (|got - want| / |want - initial|, |want - initial| /
    |initial|)}``: how far the program's leaf lies from the reference's in
    units of the reference's own change (1 for a leaf left unmoved), and
    that change as a share of the leaf (judged from ``LEAF_CHANGE_FLOOR``)."""
    import jax

    out = {}
    paths = [jax.tree_util.keystr(path) for path, _ in jax.tree_util.tree_flatten_with_path(want)[0]]
    for path, g, w, i in zip(paths, _leaves(got), _leaves(want), _leaves(initial)):
        moved = max(_sumsq(w - i), 1e-300)
        out[path] = (float(np.sqrt(_sumsq(g.astype(np.float32) - w) / moved)),
                     float(np.sqrt(moved / max(_sumsq(i), 1e-300))))
    return out


Z_MIN_SIZE = 16     # a leaf's mean and spread are held to the reference's from this many elements on
Z_SAMPLE = 1 << 20  # elements of a leaf, evenly strided, that its statistics are taken from


def initial_gaps(got: dict, module, arch: dict, seed: int) -> Tuple[float, int]:
    """The program's initial weights ``got`` against the reference
    ``module``'s own laws (``init_laws``: a leaf's shape; ``init_params`` with
    ``cap``: another seeded draw of them, ``Z_SAMPLE`` elements a leaf;
    ``init_ranges``): ``(z, outside)``. ``z`` is the largest of, in units of
    its own standard error between two sound draws and from an evenly strided
    sample of ``Z_SAMPLE`` elements of the program's leaf: the gap of a
    leaf's mean, the log ratio of its spread, and the correlation of two
    leaves of one shape (a key used twice). ``outside`` counts leaves of
    another shape, elements that differ where the law is a constant (norm
    gains) or that lie outside the leaf's stated range or are not finite
    (every element is looked at), and leaves whose sample equals another
    leaf's."""
    import jax

    is_law = lambda x: isinstance(x, tuple)
    flat_g = jax.tree_util.tree_flatten_with_path(got)[0]
    shapes = jax.tree_util.tree_flatten_with_path(
        jax.tree_util.tree_map(lambda law: law[0], module.init_laws(arch), is_leaf=is_law), is_leaf=is_law)[0]
    if [p for p, _ in flat_g] != [p for p, _ in shapes]:
        return 1e9, len(shapes)
    ranges = module.init_ranges(arch)
    z, outside = 0.0, 0
    by_shape: Dict[tuple, list] = {}
    for (path, g), (_, shape), o in zip(flat_g, shapes, _leaves(module.init_params(arch, seed, cap=Z_SAMPLE))):
        if g.shape != tuple(shape):
            outside += 1
            continue
        if o.min() == o.max():
            outside += int(np.count_nonzero(g != o[0]))
            continue
        lo, hi = ranges.get(getattr(path[-1], "key", None), (-np.inf, np.inf))
        if not (lo <= g.min() and g.max() <= hi and np.isfinite(g.min() + g.max())):  # a NaN fails every comparison
            outside += int(np.count_nonzero(~((g >= lo) & (g <= hi) & np.isfinite(g))))
        gs, os_ = g.ravel()[:: max(g.size // Z_SAMPLE, 1)].astype(np.float64), o.astype(np.float64)
        n = min(gs.size, os_.size)
        if n >= Z_MIN_SIZE:
            z = max(z, abs(gs.mean() - os_.mean()) / np.sqrt((gs.var() + os_.var()) / n),
                    abs(np.log(max(gs.std(), 1e-300) / os_.std())) * np.sqrt(n - 1.0))
        gs = gs - gs.mean()
        for other in by_shape.setdefault(g.shape, []):
            if np.array_equal(gs, other):
                outside += 1
            elif n >= Z_MIN_SIZE:
                z = max(z, abs(gs @ other) / max(np.sqrt((gs @ gs) * (other @ other)), 1e-300) * np.sqrt(n))
        by_shape[g.shape].append(gs)
    return float(min(z, 1e9)), outside


class Kind:
    def __init__(self, config: dict, cell: dict, seed: int, here: str):
        self.config, self.cell, self.seed, self.here = config, cell, seed, here
        traffic = cell["traffic"]
        self.tokens_per_row = int(traffic["tokens_per_row"])
        self.vocab = int(config["vocab_size"])
        self.batch = int(config["job_flags"]["batchSize"])
        self.n_pool = int(config["rows"]) if traffic["kind"] == "closed_loop" else 0
        self.create = create_request(config, self.tokens_per_row, seed)
        # the configuration as the reference reads it
        self.resolved = dict(config, create=self.create)
        self.counters: Dict[str, float] = {}
        self.probe_losses: List[float] = []
        self.first_update_norm = 0.0
        self.initial: Optional[dict] = None
        self.final: Optional[dict] = None
        self.scope_of: Optional[Dict[str, str]] = None
        self.leaf_gaps: Dict[str, Tuple[float, float]] = {}  # the last comparison's, leaf by leaf
        self._files_seen = 0
        self._scope_ms: Optional[Dict[str, float]] = None
        self._want_scopes = False
        self._pool: Optional[Pool] = None
        self._pool_lock = threading.Lock()

    @property
    def flops(self) -> dict:
        """Operations and bytes of one launch (``kernel_models/olmo_hybrid``)."""
        module = harness.load_module(os.path.join(self.here, "kernel_models"), self.config["reference"])
        return module.launch_counts(self.create["learner"]["dataStructure"], self.batch, self.tokens_per_row)

    # -- records --------------------------------------------------------------

    def _draw(self, stream: int, n: int) -> Rows:
        return draw_rows(gen.rng_for(self.seed, stream), n, self.tokens_per_row, self.vocab,
                         self.config["assumed"])

    def training_records(self, n: int) -> gen.Rendered:
        self.probe_rows = self._draw(gen.STREAM_PROBE, n)
        return render(self.probe_rows, False)

    def forecast_records(self, n: int) -> gen.Rendered:
        if n > self.vocab:
            raise ValueError("a forecast's id is its first token: more forecasts than the vocabulary has ids")
        rows = self._draw(gen.STREAM_FORECAST, max(n, 1))
        rows.tokens[:, 0] = np.arange(len(rows.target))
        self.forecast_rows = rows
        return render(rows, True)

    def pool(self) -> Pool:
        with self._pool_lock:
            if self._pool is None:
                self._pool = Pool(render(self._draw(gen.STREAM_POOL, self.n_pool), False))
        return self._pool

    # -- the job --------------------------------------------------------------

    def build(self, on_prediction) -> System:
        system = System(self.config, self.create, on_prediction)
        system.wait()
        self.initial = system.host_params()
        return system

    @staticmethod
    def keep(pred) -> tuple:
        return int(pred.data_instance.numerical_features[0]), float(pred.value)

    def after_probe_file(self, system: System) -> None:
        """Per-step losses; the norm of the update after the first file; the
        parameters after the last; in a traced run, the compiled launch's
        operation-to-scope table."""
        self.probe_losses += [l for l, _ in system.bridge.trainer.curve_slice()]
        self._files_seen += 1
        last = self._files_seen == len(self.cell["probe"]["files"])
        if self._files_seen == 1 or last:
            now = system.host_params()
            if self._files_seen == 1:
                self.first_update_norm = distance(now, self.initial)
            if last:
                self.final = now
        if self._want_scopes and self.scope_of is None:
            self.scope_of, memory = launch_scopes(system.bridge.trainer, self.batch, self.tokens_per_row)
            self.counters.update(memory)

    def traced_extras(self) -> Dict[str, float]:
        self._want_scopes = True
        return {}

    # -- what the readers read ------------------------------------------------

    @staticmethod
    def launches(ctx) -> list:
        """The window's executions of the launch program on the first device."""
        from perfbench import trace_reduce

        if ctx.trace is None:
            return []
        return trace_reduce.modules_in(ctx.trace, *ctx.window_ns, LAUNCH_PROGRAM)

    def scope_ms(self, ctx, scope: str) -> Optional[float]:
        """Device milliseconds a launch spends in operations under ``scope``:
        the union of their intervals inside the window's launches (a loop and
        the operations of its body overlap), over the number of launches.
        Every scope's reading goes into the line's counters
        (``lm_scope_ms.<part>``), for the breakdown in PERF.md."""
        from perfbench import trace_reduce

        if self._scope_ms is None:
            self._scope_ms = {}
            launches = self.launches(ctx)
            if launches and self.scope_of and ctx.trace.ops:
                inside: Dict[str, list] = {}
                for name, start, dur in ctx.trace.ops[ctx.trace.devices[0]]:
                    part = self.scope_of.get(name.split(" = ")[0].strip().lstrip("%"))
                    if part:
                        inside.setdefault(part, []).append((start, start + dur))
                for part, spans in inside.items():
                    total = 0.0
                    for _name, start, dur in launches:
                        clipped = [(max(a, start), min(b, start + dur)) for a, b in spans
                                   if b > start and a < start + dur]
                        total += sum(b - a for a, b in trace_reduce.merge(clipped))
                    if total:
                        self._scope_ms[part] = total / len(launches) / 1e6
                        ctx.counters["lm_scope_ms." + part.rsplit(".", 1)[-1]] = self._scope_ms[part]
        return self._scope_ms.get(scope)

    def scope_share(self, ctx, scope: str) -> Optional[float]:
        ms = self.scope_ms(ctx, scope)
        launches = self.launches(ctx)
        if ms is None or not launches:
            return None
        return 100.0 * ms / (sum(e[2] for e in launches) / len(launches) / 1e6)

    @staticmethod
    def producer_busy_share(ctx) -> Optional[float]:
        """Percent of the window the dense route's producer thread spends
        reading and parsing lines into their stage slots: self time of the
        ``read`` and ``parse_stage`` spans (the C loop fuses parse and stage;
        a ``pool_wait`` inside one is not its own time). A program without
        the span, or a route that does not write it, leaves it out."""
        from perfbench import program_spans as ps

        parts = [ps.in_window(ctx, name) for name in ("read", "parse_stage")]
        if any(p is None for p in parts) or not parts[1]:
            return None
        return 100.0 * sum(r.self_s for p in parts for r in p) / ps.window_s(ctx)

    # -- the comparison -------------------------------------------------------

    def reference(self, plans: list, precision: str = "float32", fault: Optional[str] = None,
                  initial: Optional[dict] = None) -> Readings:
        """The plain reference over the probe files, from ``initial`` (its own
        initial weights where None)."""
        module = harness.load_module(os.path.join(self.here, "reference"), self.config["reference"])
        ref = module.build(self.resolved, precision=precision, fault=fault, params=initial)
        if initial is None:
            initial = ref.host_params()
        first = 0.0
        for k, plan in enumerate(plans):
            ref.feed_file(plan.kind, plan.index, self.probe_rows, self.forecast_rows)
            if k == 0:
                first = distance(ref.host_params(), initial)
        return Readings(ref.losses, first, initial, ref.host_params(), ref.answers)

    def checks(self, plans: list, answers: List[tuple], counts: dict) -> Dict[str, dict]:
        got = Readings(self.probe_losses, self.first_update_norm, self.initial, self.final,
                       [(fid, value) for fid, value, _t in answers[: counts["probe_answers"]]])
        started = time.perf_counter()
        module = harness.load_module(os.path.join(self.here, "reference"), self.config["reference"])
        learner = self.create["learner"]
        initial = initial_gaps(self.initial, module, learner["dataStructure"], learner["hyperParameters"]["seed"])
        self.counters["initial_check_s"] = time.perf_counter() - started
        want = self.reference(plans, initial=self.initial)
        self.counters["reference_s"] = time.perf_counter() - started - self.counters["initial_check_s"]
        self.initial = self.final = None
        return self.compare(want, got, answers, counts, initial)

    def control(self, plans: list, precision: str = "float32", fault: Optional[str] = None) -> Dict[str, dict]:
        if getattr(self, "_sound", None) is None:
            self._sound = self.reference(plans)
        stand_in = self.reference(plans, precision=precision, fault=fault, initial=self._sound.initial)
        answers = [(fid, value, 0.0) for fid, value, _margin in stand_in.answers]
        counts = {"offered_rows": len(self.probe_rows.target), "fitted": len(stand_in.losses), "holdout": 0,
                  "offered_forecasts": sum(p.n_forecast for p in plans), "probe_answers": len(answers)}
        return self.compare(self._sound, stand_in, answers, counts)

    def compare(self, want: Readings, got: Readings, all_answers: List[tuple], counts: dict,
                initial: Tuple[float, int] = (0.0, 0)) -> Dict[str, dict]:
        """``initial``: :func:`initial_gaps` of the program's initial weights
        (nought where both sides are references and share theirs)."""
        out: Dict[str, float] = {}
        out["rows_lost"] = counts["offered_rows"] - counts["fitted"] - counts["holdout"]
        ids = [a[0] for a in all_answers]
        out["forecasts_bad"] = (counts["offered_forecasts"] - len(set(ids))) + (len(ids) - len(set(ids)))
        # the probe's forecasts, where the reference's two best are apart
        floor = float(self.cell["comparison"]["margin_floor"])
        judged = {fid: token for fid, token, margin in want.answers if margin > floor}
        have = {a[0]: a[1] for a in got.answers}
        out["answers_wrong"] = sum(have.get(fid) != token for fid, token in judged.items())
        self.counters["probe_answers_judged"] = len(judged)
        n = max(len(got.losses), len(want.losses))
        gaps = [1.0] * n
        for i in range(min(len(got.losses), len(want.losses))):
            gaps[i] = abs(got.losses[i] - want.losses[i]) / max(abs(want.losses[i]), 1e-3)
        out["loss_gap"] = max(gaps) if gaps else 1.0
        out["first_update_norm_gap"] = abs(got.first_update_norm - want.first_update_norm) / max(
            want.first_update_norm, 1e-30)
        if got.final is None:
            out["update_diff_rel"] = out["leaf_update_diff_rel"] = 1.0
        else:
            out["update_diff_rel"] = distance(got.final, want.final) / max(distance(want.final, want.initial), 1e-30)
            self.leaf_gaps = leaf_update_gaps(got.final, want.final, want.initial)
            judged = [gap for gap, moved in self.leaf_gaps.values() if moved >= LEAF_CHANGE_FLOOR]
            out["leaf_update_diff_rel"] = float(np.max(judged)) if judged else 1.0  # a NaN stays one
            self.counters["leaves_judged"] = len(judged)
            self.counters["leaves"] = len(self.leaf_gaps)
        out["initial_stat_z"], out["initial_outside"] = initial
        limits = self.cell["limits"]
        return {k: {"value": float(v), "limit": float(limits[k])} for k, v in out.items()}


# --- the traced run's table --------------------------------------------------

SCOPE = re.compile(r"omldm\.lm\.[a-z_]+")
LAUNCH_PROGRAM = "jit_many_dense_impl"


def launch_scopes(trainer, batch: int, tokens_per_row: int) -> Tuple[Dict[str, str], Dict[str, float]]:
    """``{operation name: scope}`` of the compiled launch program: for every
    instruction of its optimised HLO whose ``op_name`` metadata lies under a
    ``jax.named_scope`` of the model (``omldm.lm.<part>``), the innermost such
    part. The profiler's ``XLA Ops`` line names operations as the HLO does and
    keeps no scope. The program is compiled again for its text (from the
    compile cache where that is on); a program that names no scope, as a
    parent's, gives an empty table. Beside it, what the compiler says the
    launch holds on the device (``launch_argument_bytes``, the state and a
    row; ``launch_temp_bytes``, its gradients and activations, which
    ``memory_stats`` does not count)."""
    import jax

    launch = getattr(trainer, "_step_many_dense", None)
    if launch is None:
        return {}, {}
    shapes = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding), trainer.state)
    compiled = launch.lower(
        shapes, np.zeros((1, trainer.dp, batch, tokens_per_row), np.float32),
        np.zeros((1, trainer.dp, batch), np.float32),
    ).compile()
    held = compiled.memory_analysis()
    memory = {} if held is None else {"launch_argument_bytes": float(held.argument_size_in_bytes),
                                      "launch_temp_bytes": float(held.temp_size_in_bytes)}
    table = {}
    for line in compiled.as_text().splitlines():
        m = re.match(r"\s*(?:ROOT )?(%?[\w.\-]+) = ", line)
        if not m:
            continue
        name = re.search(r'op_name="([^"]*)"', line)
        scopes = SCOPE.findall(name.group(1)) if name else []
        if scopes:
            table[m.group(1).lstrip("%")] = scopes[-1]
    return table, memory

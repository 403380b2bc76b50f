"""Served LM decode on the CoMeFa grid: the system of a serving cell.

The window drives the program's `serve.engine.serve_continuous` with a
`GridLinearExecutor` on the packed engine, as a server would: weights made
once from the seed, the executor kept across calls, a closed loop of
requests whose queue never empties.  Two harness-side wrappers watch the
run without changing it:

  * `StepClock` stands in for the executor: `serve_continuous` sets its
    ``active_mask`` once per batch step, so it sees every step boundary;
    it ends the window by raising `WindowClosed` from the first
    projection after ``--seconds``, abandoning the step in flight, or -
    in a traced run - at the first step boundary after ``--seconds``, so
    the profiler's window and the spans' hold the same whole steps.  It
    keeps a seeded sample of projection calls (inputs and outputs).
  * `StepRecorder` wraps ``lm.decode_step`` to keep each step's input
    tokens and positions and a seeded sample of its logits.

After the window the plain reference (``configs/<config>_ref.py``)
recomputes the sampled projections exactly and the logits of a sample
of the window's sequences; `check` compares.
"""
from __future__ import annotations

import contextlib
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

import benchlib
import loadgen
import work
from benchlib import WindowClosed
from repro.core.comefa import isa
from repro.models import common as cm
from repro.models import lm
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.quant import bitplane
from repro.serve import engine as serve_engine
from repro.serve.comefa_exec import GridLinearExecutor

PROJECTIONS = ("q_proj", "k_proj", "v_proj", "o_proj", "gate_proj",
               "up_proj", "down_proj")
# where each projection sits in the program's parameter tree
PROGRAM_PATH = {"q_proj": ("mix", "wq"), "k_proj": ("mix", "wk"),
                "v_proj": ("mix", "wv"), "o_proj": ("mix", "wo"),
                "gate_proj": ("ffn", "wg"), "up_proj": ("ffn", "wi"),
                "down_proj": ("ffn", "wo")}
KEEP_CALLS = 64        # projection calls compared exactly, per run
KEEP_LOGITS = 64       # batch steps whose logits are kept
KEEP_SEQUENCES = 32    # sequences the reference recomputes
REF_BLOCK = 4          # sequences per reference call


class Reservoir:
    """Seeded uniform sample of at most `k` items from a stream."""

    def __init__(self, k: int, rng: np.random.Generator):
        self.k, self.rng, self.seen = k, rng, 0
        self.items: List = []

    def offer(self, item):
        """Offer an item: (kept, the item it displaced or None)."""
        self.seen += 1
        if len(self.items) < self.k:
            self.items.append(item)
            return True, None
        j = int(self.rng.integers(0, self.seen))
        if j < self.k:
            old, self.items[j] = self.items[j], item
            return True, old
        return False, None


def _annotate(on: bool, name: str):
    return jax.profiler.TraceAnnotation(name) if on else contextlib.nullcontext()


class StepClock:
    """Executor wrapper: step boundaries, the window's end, sampled calls."""

    def __init__(self, inner, rng: np.random.Generator, annotate: bool):
        self.inner = inner
        # a traced window ends at a step boundary, so the profiler's
        # window and the spans' cover the same whole steps
        self.annotate = annotate
        self.deadline: Optional[float] = None
        self.closed = False
        self.bounds: List[float] = []      # step starts, then the last end
        self.live: List[np.ndarray] = []   # live rows of each step
        self.snaps: List[Dict] = []        # counters at each boundary
        self.calls = Reservoir(KEEP_CALLS, rng)

    @property
    def active_mask(self):
        return self.inner.active_mask

    @active_mask.setter
    def active_mask(self, live):
        if not self.closed:
            self._boundary(None if live is None else np.array(live, bool))
            if (self.annotate and live is not None and self._due()):
                self.closed = True
                raise WindowClosed
        self.inner.active_mask = live

    def _due(self) -> bool:
        """Past the deadline, with at least one whole step done."""
        return (self.deadline is not None and len(self.bounds) >= 2
                and time.perf_counter() >= self.deadline)

    def _boundary(self, live) -> None:
        self.bounds.append(time.perf_counter())
        self.snaps.append(obs_metrics.flatten(obs_metrics.snapshot()))
        with obs_trace.span("bench.boundary", step=len(self.bounds) - 1):
            pass
        if live is not None:
            self.live.append(live)

    def __call__(self, params, x2, bits: int):
        if not self.annotate and self._due():
            self.closed = True
            raise WindowClosed
        with _annotate(self.annotate, "bench.grid_linear"):
            y = self.inner(params, x2, bits)
        self.calls.offer((id(params["packed"]), x2, y, self.live[-1]))
        return y


class StepRecorder:
    """Wraps ``lm.decode_step``: keeps each step's inputs and sampled logits."""

    def __init__(self, rng: np.random.Generator):
        self.steps: List[List] = []        # [token, index, logits or None]
        self.logits = Reservoir(KEEP_LOGITS, rng)
        self._orig = None

    def __enter__(self):
        self._orig = lm.decode_step
        lm.decode_step = self._step
        return self

    def __exit__(self, *exc):
        lm.decode_step = self._orig
        return False

    def _step(self, params, token, states, index, cfg, **kw):
        # copies: the caller refills its token and position buffers in place
        rec = [np.array(token), np.array(index), None]
        self.steps.append(rec)
        logits, states = self._orig(params, token, states, index, cfg, **kw)
        kept, old = self.logits.offer(len(self.steps) - 1)
        if kept:
            rec[2] = logits
        if old is not None:          # the step this one displaced
            self.steps[old][2] = None
        return logits, states


class System:
    def __init__(self, *, config: Dict, mix: Dict, seed: int,
                 rehearse: bool, reference):
        self.cfg = dict(config)
        if rehearse:
            self.cfg.update(config["rehearsal"])
        self.dep = config["deployment"]
        self.mix = mix
        self.seed = seed
        self.ref = reference
        self.engine = "pallas" if rehearse else self.dep["engine"]
        self.expect_mode = "interpret" if rehearse else "compiled"
        c = self.cfg
        self.dims = dict(
            hidden=c["hidden_size"], intermediate=c["intermediate_size"],
            heads=c["num_attention_heads"], kv_heads=c["num_key_value_heads"],
            head_dim=c.get("head_dim", c["assumed"]["head_dim"]),
            layers=c["num_hidden_layers"], vocab=c["vocab_size"],
            eps=c["rms_norm_eps"], rope_theta=c["rope_theta"])
        self.slots = self.dep["slots"]
        self.max_len = mix["max_len"]
        self.x_bits = self.dep["activation_bits"]

    # -- set-up --------------------------------------------------------------
    def program_config(self) -> cm.Config:
        d = self.dims
        return cm.Config(
            name=self.cfg["name"], n_layers=d["layers"], d_model=d["hidden"],
            n_heads=d["heads"], kv_heads=d["kv_heads"],
            d_ff=d["intermediate"], vocab=d["vocab"], head_dim=d["head_dim"],
            pattern=(("global", "mlp"),), rope_theta=d["rope_theta"],
            norm_eps=d["eps"], act=self.cfg["hidden_act"],
            tie_embeddings=self.cfg["tie_word_embeddings"],
            quant_bits=self.dep["weight_bits"], dtype=self.dep["dtype"],
            scan_layers=self.dep["scan_layers"], remat=False)

    def _shapes(self) -> Dict:
        d = self.dims
        q_out = d["heads"] * d["head_dim"]
        kv_out = d["kv_heads"] * d["head_dim"]
        h, f = d["hidden"], d["intermediate"]
        return {"q_proj": (h, q_out), "k_proj": (h, kv_out),
                "v_proj": (h, kv_out), "o_proj": (q_out, h),
                "gate_proj": (h, f), "up_proj": (h, f), "down_proj": (f, h)}

    def _make(self, key):
        """Canonical weights and the program's parameter tree, from a key."""
        d, bits = self.dims, self.dep["weight_bits"]
        std = self.cfg["initializer_range"]
        dtype = jnp.dtype(self.dep["dtype"])
        shapes = self._shapes()
        keys = iter(jax.random.split(key, 1 + len(shapes) * d["layers"]))
        embed = (jax.random.normal(next(keys), (d["vocab"], d["hidden"]),
                                   jnp.float32) * std).astype(dtype)
        qmax = 2.0 ** (bits - 1) - 1
        layers, groups = [], []
        zeros = jnp.zeros((d["hidden"],), jnp.float32)   # (1 + g) = 1
        for _ in range(d["layers"]):
            lw, prog = {}, {"n1": {"g": zeros}, "n2": {"g": zeros},
                            "mix": {}, "ffn": {}}
            for name in PROJECTIONS:
                w = jax.random.normal(next(keys), shapes[name],
                                      jnp.float32) * std
                absmax = jnp.max(jnp.abs(w), axis=0)
                scale = jnp.where(absmax > 0, absmax / qmax, 1.0)
                q = jnp.clip(jnp.round(w / scale), -qmax - 1, qmax)
                lw[name] = (q.astype(jnp.int8), scale.astype(jnp.float32))
                part, leaf = PROGRAM_PATH[name]
                prog[part][leaf] = {
                    "packed": bitplane.pack(q.astype(jnp.int32), bits, axis=0),
                    "scale": scale.astype(jnp.float32)[None, :]}
            layers.append(lw)
            groups.append({"l0": prog})
        canonical = {"embed": embed, "layers": layers}
        program = {"embed": {"e": embed}, "nf": {"g": zeros},
                   "stack": {"group_list": groups, "rem": []}}
        return canonical, program

    def setup(self, annotate: bool) -> None:
        self.pcfg = self.program_config()
        key = benchlib.jax_key(jax, self.seed)
        self.weights, self.params = jax.jit(self._make)(key)
        expected = jax.eval_shape(
            lambda: lm.init(jax.random.PRNGKey(0), self.pcfg))
        got = jax.eval_shape(lambda: self.params)
        if jax.tree.structure(expected) != jax.tree.structure(got) or any(
                (a.shape, a.dtype) != (b.shape, b.dtype) for a, b in
                zip(jax.tree.leaves(expected), jax.tree.leaves(got))):
            raise RuntimeError("the program's parameter tree is not the one "
                               "this module builds")
        self.packed_name = {}
        for l, group in enumerate(self.params["stack"]["group_list"]):
            for name, (part, leaf) in PROGRAM_PATH.items():
                self.packed_name[id(group["l0"][part][leaf]["packed"])] = (
                    l, name)
        self.requests = [serve_engine.Request(p, n) for p, n in
                         loadgen.requests(self.mix, self.seed,
                                          self.dims["vocab"])]
        self.executor = GridLinearExecutor(
            slots=self.slots, x_bits=self.x_bits, recode=self.dep["recode"],
            backend="grid", engine=self.engine)
        self.annotate = annotate
        # warm-up: one batch step that admits, decodes and retires a row
        # per slot, so every shape of the window is compiled
        wr = loadgen.rng(self.seed, "warmup")
        warm = [serve_engine.Request(
            wr.integers(0, self.dims["vocab"], size=1).astype(np.int32), 1)
            for _ in range(self.slots)]
        with StepRecorder(wr):
            serve_engine.serve_continuous(
                self.params, warm, self.pcfg, slots=self.slots,
                max_len=self.max_len, executor=StepClock(
                    self.executor, wr, annotate=False),
                temperature=self.mix["temperature"])

    # -- the window ------------------------------------------------------------
    def window(self, t0: float, seconds: float) -> Dict:
        rng = loadgen.rng(self.seed, "sample")
        self.clock = StepClock(self.executor, rng, self.annotate)
        self.clock.deadline = t0 + seconds
        self.recorder = StepRecorder(rng)
        self.snap0 = obs_metrics.flatten(obs_metrics.snapshot())
        self.outputs = None
        with obs_trace.span("bench.window_start"):
            pass
        with self.recorder, _annotate(self.annotate, "bench.serve_continuous"):
            try:
                self.outputs = serve_engine.serve_continuous(
                    self.params, self.requests, self.pcfg, slots=self.slots,
                    max_len=self.max_len, executor=self.clock,
                    temperature=self.mix["temperature"])
            except WindowClosed:
                pass
        t_abort = time.perf_counter()
        b = self.clock.bounds
        # steps completed by the last boundary at or before the deadline
        # (the first step's end if even that came later)
        ends = [i for i in range(1, len(b)) if b[i] <= self.clock.deadline]
        k = ends[-1] if ends else min(1, len(b) - 1)
        if self.annotate:
            k = len(b) - 1
        self.k = k
        tokens = int(sum(int(m.sum()) for m in self.clock.live[:k]))
        window_s = (b[k] - t0) if k else (t_abort - t0)
        ctx = [int(np.asarray(rec[1])[m].mean()) + 1 for rec, m in
               zip(self.recorder.steps[:k], self.clock.live[:k]) if m.any()]
        self.result = dict(
            window_s=window_s, tokens=tokens, steps=k,
            decode_tok_s=tokens / window_s if k else None,
            context_mean=float(np.mean(ctx)) if ctx else 1.0,
            window_end_snap=self.clock.snaps[k] if k else self.snap0)
        return self.result

    def end_to_end(self) -> Dict[str, float]:
        out = {}
        if self.result["decode_tok_s"]:
            out["decode_tok_s"] = self.result["decode_tok_s"]
        return out

    def attempted(self) -> int:
        return self.result["tokens"]

    def facts(self) -> Dict:
        d = self.dims
        flops = work.lm_flops_per_token(
            hidden=d["hidden"], intermediate=d["intermediate"],
            heads=d["heads"], kv_heads=d["kv_heads"], head_dim=d["head_dim"],
            layers=d["layers"], vocab=d["vocab"],
            context=self.result["context_mean"])
        return dict(tokens=self.result["tokens"],
                    decode_tok_s=self.result["decode_tok_s"],
                    flops_per_token=flops, n_fields=isa.N_ENGINE_FIELDS)

    def span_window(self, events) -> tuple:
        """The window in the obs tracer's clock (microseconds): from its
        start to the end of its last counted step."""
        t_lo = max(e.ts for e in events if e.name == "bench.window_start")
        ends = [e.ts for e in events if e.name == "bench.boundary"
                and e.attrs.get("step") == self.k and e.ts >= t_lo]
        return t_lo, (ends[0] if ends else max(e.ts + e.dur for e in events))

    def free(self) -> None:
        """Drop the program's state before the reference runs."""
        self.params = None
        self.executor = None

    # -- correctness -------------------------------------------------------------
    def _sequences(self) -> List[Dict]:
        """The window's rows as sequences: the tokens fed at each
        position, and the program's logits where they were kept."""
        seqs, open_row = [], {}
        n_admitted = 0
        for s, (tok, idx, logits) in enumerate(self.recorder.steps):
            tok, idx = tok.reshape(-1), idx.reshape(-1)
            rows = None if logits is None else np.asarray(
                logits[:, -1], np.float32)
            for g in np.flatnonzero(self.clock.live[s]):
                p = int(idx[g])
                if p == 0:
                    req = self.requests[n_admitted]
                    open_row[g] = dict(request=n_admitted,
                                       prompt=np.asarray(req.prompt),
                                       tokens=[], logits={})
                    seqs.append(open_row[g])
                    n_admitted += 1
                seq = open_row[g]
                if p != len(seq["tokens"]):
                    raise RuntimeError(f"row {g} jumped to position {p}")
                seq["tokens"].append(int(tok[g]))
                if rows is not None:
                    seq["logits"][p] = rows[g]
        for seq in seqs:
            n = min(len(seq["prompt"]), len(seq["tokens"]))
            if not np.array_equal(seq["tokens"][:n], seq["prompt"][:n]):
                raise RuntimeError(
                    f"request {seq['request']}: the row replayed another "
                    "prompt (admission is not first-in first-out)")
        if self.outputs is not None:          # the mix ran to its end
            for seq in seqs:
                out = self.outputs[seq["request"]]
                seq["tokens"] = list(seq["prompt"]) + [int(t) for t in out]
        return seqs

    def _pick(self, seqs: List[Dict]) -> List[Dict]:
        if len(seqs) <= KEEP_SEQUENCES:
            return seqs
        r = loadgen.rng(self.seed, "check")
        longest = max(range(len(seqs)), key=lambda i: len(seqs[i]["tokens"]))
        rest = [i for i in range(len(seqs)) if i != longest]
        pick = [longest] + list(r.choice(rest, KEEP_SEQUENCES - 1,
                                         replace=False))
        return [seqs[i] for i in sorted(pick)]

    def _ref_logits(self, seqs: List[Dict], x_bits: int) -> List[np.ndarray]:
        """Reference logits at every position of each sequence."""
        fwd = jax.jit(lambda w, t: self.ref.forward(w, t, self.dims, x_bits))
        out = []
        for i in range(0, len(seqs), REF_BLOCK):
            block = seqs[i:i + REF_BLOCK]
            toks = np.zeros((REF_BLOCK, self.max_len), np.int32)
            for j, seq in enumerate(block):
                toks[j, :len(seq["tokens"])] = seq["tokens"]
            logits = np.asarray(fwd(self.weights, jnp.asarray(toks)))
            out += [logits[j, :len(seq["tokens"])]
                    for j, seq in enumerate(block)]
        return out

    @staticmethod
    def _gaps(seq: Dict, ref: np.ndarray, logits: Dict) -> List[float]:
        """How far below the reference's best each produced token lies:
        every served token (fed back after the prompt), and the top token
        of each kept logits row (`logits`, position -> row)."""
        best = ref.max(axis=-1)
        lp = max(len(seq["prompt"]), 1)
        gaps = [best[q - 1] - ref[q - 1, seq["tokens"][q]]
                for q in range(lp, len(seq["tokens"]))]
        gaps += [best[p] - ref[p, int(np.argmax(row))]
                 for p, row in logits.items()]
        return gaps

    @staticmethod
    def _rel_rms(ref: np.ndarray, logits: Dict) -> List[float]:
        """Distance of each kept logits row from the reference's, over the
        spread of the reference's row (root mean squares over the vocab)."""
        out = []
        for p, row in logits.items():
            want = ref[p]
            spread = np.sqrt(np.mean((want - want.mean()) ** 2))
            out.append(float(np.sqrt(np.mean((row - want) ** 2)) / spread))
        return out

    def check(self, control: bool) -> Dict:
        checks, info = {}, {}
        limits = self.cfg["limits"]

        def judged(name, values):
            lim = limits[name]
            checks[name] = dict(value=max(values) if values else None,
                                limit=lim, bad=sum(v > lim for v in values))

        with jax.default_matmul_precision("highest"):
            # the sampled projection calls, against the exact answer; the
            # control is the reference at half the activation bits
            errs = []
            for pid, x2, y, live in self.clock.calls.items:
                layer, name = self.packed_name[pid]
                q_w, s_w = (np.asarray(a) for a in
                            self.weights["layers"][layer][name])
                rows = np.flatnonzero(live)
                x = np.asarray(x2, np.float32)[rows]
                want = self.ref.projection(x, q_w, s_w, self.x_bits)
                if control:
                    got = self.ref.projection(x, q_w, s_w, self.x_bits // 2)
                else:
                    got = np.asarray(y, np.float32)[rows]
                scale = max(float(np.abs(want).max()), 1e-30)
                errs.append(float(np.abs(got - want).max()) / scale)
            judged("proj_rel_err", errs)
            # the window's sequences through the reference model
            seqs = self._pick(self._sequences())
            ref = self._ref_logits(seqs, self.x_bits)
            if control:
                # the control's logits at the positions the program's
                # were kept, and no served token of its own
                low = self._ref_logits(seqs, self.x_bits // 2)
                seqs = [dict(seq, tokens=[],
                             logits={q: lo[q] for q in seq["logits"]})
                        for seq, lo in zip(seqs, low)]
            judged("logit_gap", [float(g) for seq, r in zip(seqs, ref)
                                 for g in self._gaps(seq, r, seq["logits"])])
            judged("logit_rel_rms", [e for seq, r in zip(seqs, ref)
                                     for e in self._rel_rms(r, seq["logits"])])
        info["sequences"] = len(seqs)
        info["positions"] = sum(len(r) for r in ref)
        return dict(checks=checks, info=info)

    def validity(self, counters: Dict[str, float]) -> List[str]:
        """Reasons the window is no sound run (empty when it is)."""
        bad = []
        grid = sum(v for k, v in counters.items()
                   if k.startswith("comefa.dispatches{")
                   and "kind=grid" in k)
        pallas = sum(v for k, v in counters.items()
                     if k.startswith("comefa.dispatches{")
                     and "kind=grid" in k and "engine=pallas" in k)
        mode = sum(v for k, v in counters.items()
                   if k == f"comefa.pallas_calls{{mode={self.expect_mode}}}")
        if grid <= 0:
            bad.append("the window made no grid dispatch")
        if pallas != grid or mode != sum(
                v for k, v in counters.items()
                if k.startswith("comefa.pallas_calls{")):
            bad.append(f"a grid dispatch ran off engine=pallas / "
                       f"mode={self.expect_mode}")
        return bad

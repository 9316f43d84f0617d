"""The comparison that decides ``correct``.

Once the window has closed and the program's state is freed, a plain
RAR (written here from the paper's procedure, sharing no code with the
program) replays every request of the run in the order the program
served its microbatches, against its own copy of the planted store, with
its own float32 embeddings. For each request it works out the route, the
tier calls the route makes (prompts, spliced guides, guide requests),
the user-visible outcome and the store writes. Where a similarity lies
within ``tie_sim`` of a threshold or of the runner-up, both readings are
plausible and the program's choice is taken. Answer tokens come from the
program's own calls (they decide alignment); on a seeded sample of
requests the reference runs each tier once over every prompt the program
served and reads how far each served token's logit lies below its best.

The numbers compared are those the configuration's ``limits`` name,
each held to its limit there:

* ``embed_gap``: the largest |program - reference| component of a
  sampled request's embedding; ``embed_rms``: the root mean square of
  program - reference over every component of the sampled embeddings;
* ``weak_gap`` / ``strong_gap``: the largest logit gap of a token served
  by that tier on the sample (both guide tokens included, the second one
  decoded through the cache); ``weak_mean_gap``: the mean of those gaps
  over the weak tier's served tokens. A request whose calls or outcome
  differ from the replay sets its tiers' gaps to infinity;
* ``store_gap`` / ``store_rms``: the same two of the embeddings the run
  committed to the store; a committed slot whose flags, guide or time
  stamp differ, or a commit too many or too few, sets them to infinity.

With ``control`` set, the reference in a lower precision is put in the
program's place (``CONTROL``): the same sampled prompts and served
tokens run through it, and at the position of each served token the gap
of the token that it puts first is read; the bfloat16 embedder embeds
the sampled and the committed requests. Its readings of the same numbers
go through the same :func:`judge` as the program's.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np

from bench import reference as R
from bench.probes import request_key
from bench.traffic import GUIDE_END, GUIDE_START, PAD

OPTION_A = 8          # the program's answer tokens are OPTION_A .. +3
CONTROL_NUMERICS = {"tiers": ("int8", "fp8"), "embedder": "bf16"}
# the control's reading of each number: the tiers one step below
# bfloat16 in fp8 (int8 is read beside it), the float32 embedder in
# bfloat16
CONTROL = {"embed_gap": "control_bf16_embed_gap",
           "embed_rms": "control_bf16_embed_rms",
           "store_rms": "control_bf16_store_rms",
           "weak_gap": "control_fp8_weak_gap",
           "weak_mean_gap": "control_fp8_weak_mean_gap",
           "strong_gap": "control_fp8_strong_gap",
           "store_gap": "control_bf16_store_gap"}


@dataclasses.dataclass
class Inputs:
    """What a run hands the check. ``batches`` lists the request ids of
    each microbatch in the order the program served them; ``outcomes``
    maps a request id to its ``(served_by, strong_calls, case)``;
    ``calls`` are the tier calls of the served stretches."""
    config: dict
    requests: dict            # rid -> traffic.Request
    batches: list             # [[rid, ...], ...] in serve order
    outcomes: dict
    calls: list               # probes.Call
    prog_emb: dict            # request key -> program embedding
    sim_threshold: float
    guide_threshold: float
    reprobe_period: int
    planted: tuple            # device (emb (Cp, Ep), mask, guide, hard, added_at)
    store_after: dict         # host arrays of the program's store
    weights: dict             # "weak" / "strong" / "embedder" params
    sample: list              # request ids whose tier calls are compared
    tail: int                 # question tail length that names a request


def answer(token: int) -> int:
    a = int(token) - OPTION_A
    return a if 0 <= a <= 3 else -1


def aligned(a: int, b: int) -> bool:
    return a == b and a >= 0


def splice(prompt: np.ndarray, guide) -> np.ndarray:
    g = np.asarray(guide)
    return np.concatenate([prompt[:1], g[g != PAD], prompt[1:]]).astype(
        np.int32)


def fresh_guide(tokens, G: int) -> np.ndarray:
    g = np.full((G,), PAD, np.int32)
    g[0], g[1:3], g[3] = GUIDE_START, tokens, GUIDE_END
    return g


class _Store:
    """The reference's store: planted rows at and above ``lo`` are read
    through a precomputed top-T, rows below ``lo`` (the only ones a run
    can overwrite) are kept on the host and written as the replay
    commits."""

    def __init__(self, inp: Inputs, q_all: np.ndarray, rid_row: dict):
        import jax.numpy as jnp
        st = inp.config["store"]
        self.C, E, self.G = st["capacity"], st["embed_dim"], st["guide_len"]
        emb, mask, guide, hard, added_at = inp.planted
        self.lo = min(self.C, len(inp.requests) + 8)
        rows = emb[:self.C, :E]
        has_guide = (mask[:self.C, 0] & 2) != 0
        T = 8
        valid = jnp.ones((self.C,), bool)
        self.top = R.store_topk(rows, valid, q_all, T, self.lo)
        self.top_g = R.store_topk(rows, has_guide, q_all, T, self.lo)
        self.rid_row = rid_row
        self.guide = np.asarray(guide).copy()
        self.hard = np.asarray(hard).copy()
        self.added_at = np.asarray(added_at).copy()
        self.has_guide = np.asarray(has_guide).copy()
        self.low = np.asarray(rows[:self.lo], np.float64)
        self.ptr = self.C
        self.writes: list[tuple] = []     # (slot, rid, guide, hg, hard, now)

    def candidates(self, rid: int, q: np.ndarray, guides_only: bool):
        """[(sim, row)] best first: the precomputed planted top-T plus
        every row below ``lo`` in its current state."""
        top = self.top_g if guides_only else self.top
        j = self.rid_row[rid]
        out = [(float(s), int(r)) for s, r in zip(top[0][j], top[1][j])
               if np.isfinite(s)]
        sims = self.low @ q.astype(np.float64)
        ok = self.has_guide[:self.lo] if guides_only else \
            np.ones(self.lo, bool)
        out += [(float(sims[r]), int(r)) for r in np.flatnonzero(ok)]
        out.sort(key=lambda x: (-x[0], x[1]))
        return out


def _route_options(st: _Store, cands, now: int, inp: Inputs, tau: float):
    """Plausible routes ``(group, row)`` of a request whose best
    candidates are ``cands``; the first one is the reference's own."""
    s1 = cands[0][0]
    opts = []
    close = [(s, r) for s, r in cands if s >= s1 - tau]
    if s1 >= inp.sim_threshold - tau:
        for s, r in close:
            if s < inp.sim_threshold - tau:
                continue
            if st.hard[r]:
                if now - int(st.added_at[r]) < inp.reprobe_period:
                    opts.append(("memory_hard", r))
                else:
                    opts.append(("shadow", r))
            elif st.has_guide[r]:
                opts.append(("memory_guide", r))
            else:
                opts.append(("memory_skill", r))
    if s1 < inp.sim_threshold + tau:
        miss = ("shadow", None)
        if s1 < inp.sim_threshold:
            opts.insert(0, miss)
        else:
            opts.append(miss)
    return opts


class Replay:
    def __init__(self, inp: Inputs, ref_emb: dict, tau: float):
        self.inp = inp
        self.tau = tau
        self.ref_emb = ref_emb
        rids = [rid for b in inp.batches for rid in b]
        self.rid_row = {rid: i for i, rid in enumerate(rids)}
        q_all = np.stack([ref_emb[rid] for rid in rids])
        self.st = _Store(inp, q_all, self.rid_row)
        self.G = self.st.G
        # observed calls by request: answers by question tail, guide
        # generations by guide request (in serve order)
        self.ans: dict[bytes, list] = {}
        self.gen: dict[bytes, list] = {}
        for c in inp.calls:
            for p, tok in zip(c.prompts, c.tokens):
                if c.max_new == 1:
                    self.ans.setdefault(request_key(p, inp.tail), []).append(
                        (c.tier, np.asarray(p), tok))
                else:
                    self.gen.setdefault(np.asarray(p).tobytes(), []).append(
                        (c.tier, np.asarray(p), tok))
        self.mismatch: dict[int, str] = {}
        self.expected: dict[int, list] = {}   # rid -> [(tier, prompt, toks)]

    def _observed(self, req):
        return self.ans.get(request_key(req.prompt, self.inp.tail), [])

    def _take_gen(self, req):
        lst = self.gen.get(req.greq.tobytes(), [])
        return lst.pop(0) if lst else None

    def run(self):
        now = 0
        for batch in self.inp.batches:
            staged, flags = [], []
            ptr_at_batch = self.st.ptr
            for rid in batch:
                now += 1
                self._one(rid, now, staged, flags)
            for slot_now, rid, guide, hg, hard in sorted(staged,
                                                         key=lambda x: x[0]):
                slot = self.st.ptr % self.st.C
                self.st.ptr += 1
                if slot >= self.st.lo:
                    raise RuntimeError("a run committed past the replay's "
                                       "host rows")
                self.st.low[slot] = self.ref_emb[rid]
                self.st.guide[slot] = guide
                self.st.has_guide[slot] = hg
                self.st.hard[slot] = hard
                self.st.added_at[slot] = slot_now
                self.st.writes.append((slot, rid, guide, hg, hard, slot_now))
            covered = self.st.ptr - ptr_at_batch
            for kind, row, t in flags:
                if (row - ptr_at_batch) % self.st.C < covered:
                    continue          # the slot was overwritten meanwhile
                if kind == "clear":
                    self.st.hard[row] = False
                else:
                    self.st.added_at[row] = t
        return self

    def _one(self, rid, now, staged, flags):
        inp, st = self.inp, self.st
        req = inp.requests[rid]
        q = self.ref_emb[rid]
        obs = self._observed(req)
        out = inp.outcomes.get(rid)
        opts = _route_options(st, st.candidates(rid, q, False), now, inp,
                              self.tau)
        group_obs = None
        if out is not None:
            case = out[2]
            group_obs = case if case.startswith("memory_") else "shadow"
        choice = None
        for g, row in opts:
            if g != group_obs:
                continue
            if g == "memory_guide":
                want = splice(req.prompt, st.guide[row])
                if not any(t == "weak" and np.array_equal(p, want)
                           for t, p, _ in obs):
                    continue
            choice = (g, row)
            break
        if choice is None:
            self.mismatch[rid] = f"route {group_obs} not in {opts[:3]}"
            choice = opts[0]
        group, row = choice
        calls = []                         # (tier, prompt, max_new)
        if group == "memory_hard":
            calls.append(("strong", req.prompt, 1))
            exp = ("strong", 1, "memory_hard")
        elif group == "memory_guide":
            calls.append(("weak", splice(req.prompt, st.guide[row]), 1))
            exp = ("weak", 0, "memory_guide")
        elif group == "memory_skill":
            calls.append(("weak", req.prompt, 1))
            exp = ("weak", 0, "memory_skill")
        else:
            exp = self._shadow(rid, req, q, now, row, obs, calls, staged,
                               flags)
        self.expected[rid] = calls
        if out is not None and tuple(out) != exp:
            self.mismatch.setdefault(rid, f"outcome {out} != {exp}")
        want = sorted((t, np.asarray(p, np.int32).tobytes())
                      for t, p, m in calls if m == 1)
        got = sorted((t, np.asarray(p, np.int32).tobytes())
                     for t, p, _ in obs)
        if want != got:
            self.mismatch.setdefault(rid, f"answer calls {len(got)} != "
                                          f"{len(want)} expected")

    def _token(self, obs, tier, prompt):
        for t, p, tok in obs:
            if t == tier and np.array_equal(p, prompt):
                return tok
        return None

    def _shadow(self, rid, req, q, now, reprobe_row, obs, calls, staged,
                flags):
        inp, st = self.inp, self.st
        calls.append(("strong", req.prompt, 1))
        calls.append(("weak", req.prompt, 1))
        s_tok = self._token(obs, "strong", req.prompt)
        w_tok = self._token(obs, "weak", req.prompt)
        strong_a = answer(s_tok[0]) if s_tok is not None else -1
        strong_calls, stage, guide = 1, "case3", np.zeros(self.G, np.int32)
        if w_tok is not None and aligned(answer(w_tok[0]), strong_a):
            stage = "case1"
        else:
            gc = st.candidates(rid, q, True)
            gs, grow = gc[0] if gc else (-2.0, None)
            probe = gs >= inp.guide_threshold
            if abs(gs - inp.guide_threshold) <= self.tau and grow is not None:
                # either way is plausible: follow the program
                probe = self._token(obs, "weak", splice(
                    req.prompt, st.guide[grow])) is not None
            if probe and grow is not None:
                p2a = splice(req.prompt, st.guide[grow])
                calls.append(("weak", p2a, 1))
                t2a = self._token(obs, "weak", p2a)
                if t2a is not None and aligned(answer(t2a[0]), strong_a):
                    stage, guide = "case2a", st.guide[grow]
            if stage == "case3":
                gen = self._take_gen(req)
                calls.append(("strong", req.greq, 2))
                strong_calls += 1
                if gen is None:
                    self.mismatch.setdefault(rid, "no guide generation")
                else:
                    self.expected.setdefault(("gen", rid), gen)
                    fg = fresh_guide(gen[2][:2], self.G)
                    p2b = splice(req.prompt, fg)
                    calls.append(("weak", p2b, 1))
                    t2b = self._token(obs, "weak", p2b)
                    if t2b is not None and aligned(answer(t2b[0]), strong_a):
                        stage, guide = "case2b", fg
        reprobe = reprobe_row is not None
        if stage == "case3":
            if reprobe:
                flags.append(("touch", reprobe_row, now))
            else:
                staged.append((now, rid, np.zeros(self.G, np.int32), False,
                               True))
            case = "case3"
        else:
            staged.append((now, rid, guide, stage != "case1", False))
            if reprobe:
                flags.append(("clear", reprobe_row, now))
            case = {"case1": "case1_reprobe" if reprobe else "case1"}.get(
                stage, "case2")
        return ("strong", strong_calls, case)


def _tier_gaps(cfg, params, items, control: tuple[str, ...],
               block: int = 32) -> dict:
    """``items``: [(prompt, tokens, n)]: the reference reads the logits at
    the positions of the ``n`` served tokens (over the prompt and the
    served tokens but the last). For the served tokens, and for each
    lower precision in ``control`` the tokens that it puts first at those
    same positions, returns the widest gap (``gap``) and the mean gap
    (``mean_gap``), keyed ``""`` for the served tokens and by name."""
    gaps: dict[str, list] = {name: [] for name in ("",) + tuple(control)}
    groups: dict[tuple, list] = {}
    for prompt, toks, n in items:
        seq = np.concatenate([prompt, toks[:n - 1]]).astype(np.int32)
        groups.setdefault((len(seq), n), []).append((seq, toks[:n]))
    for (S, n), lst in sorted(groups.items()):
        for i in range(0, len(lst), block):
            chunk = lst[i:i + block]
            seqs = np.stack([s for s, _ in chunk] +
                            [chunk[0][0]] * (block - len(chunk)))
            ref = np.asarray(R.tier_logits(cfg, params, seqs, n))[:len(chunk)]
            best = ref.max(-1)                           # (b, n)
            served = np.stack([t for _, t in chunk]).astype(np.int64)
            got = np.take_along_axis(ref, served[..., None], -1)[..., 0]
            gaps[""].append((best - got).ravel())
            for name in control:
                lo = np.asarray(R.tier_logits(cfg, params, seqs, n,
                                              name))[:len(chunk)]
                top = np.take_along_axis(ref, lo.argmax(-1)[..., None],
                                         -1)[..., 0]
                gaps[name].append((best - top).ravel())
    out = {}
    for name, parts in gaps.items():
        g = np.concatenate(parts) if parts else np.zeros(1)
        out[name] = {"gap": float(np.max(g)), "mean_gap": float(np.mean(g))}
    return out


def _row_gaps(a: np.ndarray, b: np.ndarray) -> tuple[float, float]:
    """The largest |a - b| component, and the root mean square of a - b
    over every component."""
    d = np.asarray(a, np.float64) - np.asarray(b, np.float64)
    return float(np.max(np.abs(d))), float(np.sqrt(np.mean(d * d)))


def judge(checks: dict) -> bool:
    """Every compared number finite and within its limit."""
    return all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
               for c in checks.values())


def program_checks(readings: dict, limits: dict) -> dict:
    """The numbers a configuration compares (those its ``limits`` name),
    each beside its limit."""
    return {k: {"value": readings[k], "limit": v} for k, v in limits.items()}


def control_checks(readings: dict, limits: dict) -> dict:
    """The control's readings of the same numbers, beside the same
    limits."""
    return {k: {"value": readings[CONTROL[k]], "limit": v}
            for k, v in limits.items()}


def run(inp: Inputs, *, control: bool = False) -> dict:
    """Every reading of the run (and with ``control`` the control's)."""
    cfg = inp.config
    tau = float(cfg["tie_sim"])
    rids = [rid for b in inp.batches for rid in b]
    prompts = np.stack([inp.requests[r].prompt for r in rids])
    ref = R.embed_many(cfg["embedder"], inp.weights["embedder"], prompts)
    ref_emb = {rid: ref[i] for i, rid in enumerate(rids)}
    rep = Replay(inp, ref_emb, tau).run()

    def prog(rid):
        return inp.prog_emb.get(request_key(inp.requests[rid].prompt,
                                            inp.tail))

    sample = [r for r in inp.sample if r in ref_emb]
    embs = [prog(rid) for rid in sample]
    if any(e is None for e in embs) or not embs:
        embed_gap = embed_rms = math.inf
    else:
        embed_gap, embed_rms = _row_gaps(
            np.stack(embs), np.stack([ref_emb[r] for r in sample]))

    items = {"weak": [], "strong": []}
    inf = {"weak": False, "strong": False}
    for rid in sample:
        if rid in rep.mismatch:
            inf["weak"] = inf["strong"] = True
            continue
        obs = rep._observed(inp.requests[rid])
        for tier, prompt, n in rep.expected.get(rid, []):
            tok = rep._token(obs, tier, prompt) if n == 1 else \
                rep.expected.get(("gen", rid), (None, None, None))[2]
            if tok is None:
                inf[tier] = True
                continue
            items[tier].append((prompt, np.asarray(tok), n))
    out = {"embed_gap": embed_gap, "embed_rms": embed_rms,
           "sampled": len(sample),
           "mismatched": len(rep.mismatch),
           "mismatch_examples": dict(list(rep.mismatch.items())[:3])}
    ctl_names = CONTROL_NUMERICS["tiers"] if control else ()
    for tier in ("weak", "strong"):
        read = _tier_gaps(cfg[tier], inp.weights[tier], items[tier],
                          ctl_names)
        for name, stats in read.items():
            for stat, v in stats.items():
                if name:
                    out[f"control_{name}_{tier}_{stat}"] = v
                else:
                    out[f"{tier}_{stat}"] = math.inf if inf[tier] else v
        out[f"{tier}_tokens"] = sum(n for _, _, n in items[tier])

    # the store after the run's commits
    sa = inp.store_after
    n_prog = int(sa["ptr"]) - rep.st.C
    same = n_prog == len(rep.st.writes)
    for slot, rid, guide, hg, hard, t in rep.st.writes:
        same = same and slot < len(sa["hard"]) and (
            bool(sa["has_guide"][slot]) == bool(hg)
            and bool(sa["hard"][slot]) == bool(hard)
            and int(sa["added_at"][slot]) == int(t)
            and np.array_equal(sa["guide"][slot], guide))
    if same and rep.st.writes:
        rows = sa["emb"][[w[0] for w in rep.st.writes]]
        ref_rows = np.stack([ref_emb[w[1]] for w in rep.st.writes])
        out["store_gap"], out["store_rms"] = _row_gaps(rows, ref_rows)
    else:
        out["store_gap"] = out["store_rms"] = 0.0 if same else math.inf
    out["commits"] = len(rep.st.writes)
    if control:
        name = CONTROL_NUMERICS["embedder"]
        for key, rids in (("embed", sample),
                          ("store", [w[1] for w in rep.st.writes])):
            if not rids:
                out[f"control_{name}_{key}_gap"] = 0.0
                out[f"control_{name}_{key}_rms"] = 0.0
                continue
            low = R.embed_many(cfg["embedder"], inp.weights["embedder"],
                               np.stack([inp.requests[r].prompt
                                         for r in rids]), numerics=name)
            (out[f"control_{name}_{key}_gap"],
             out[f"control_{name}_{key}_rms"]) = _row_gaps(
                low, np.stack([ref_emb[r] for r in rids]))
    return out

"""The one traffic generator: turns a mix file (``mixes/<name>.json``),
a rate, a window length and a seed into the requests of one run.

Every request is a 256-token prompt: BOS, a per-skill part shared by
every request of the skill (drawn from the skill's own seeded slice of
the vocabulary), a question of its own, and the answer marker. Its guide
request is the first tokens of the prompt plus a fixed request block.
Known skills are planted in the store before the run; new skills are
drawn without repetition from a large pool, so each is seen once.

The seed changes content, never the work: the arrival instants, which
arrivals come from known skills and at which popularity rank, and the
kind (guide, bare, hard) of the skill at each rank are drawn from the
mix's own fixed seed. The run's seed draws the weights' inputs: which
skill id holds each rank, the new skills, and every token.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from bench import loadgen

# token ids of the program's vocabulary layout: content tokens start above
# the special ids, and every prompt starts with BOS and ends with ANS
PAD, BOS, ANS, GUIDE_START, GUIDE_END, GUIDE_REQ = 0, 1, 4, 5, 6, 7
KINDS = ("guide", "bare", "hard")


@dataclasses.dataclass
class Request:
    rid: int                 # index over the run: warm-up first, then window
    t: float                 # seconds after its stretch starts
    skill: int
    kind: str                # a known skill's kind, or "new"
    prompt: np.ndarray       # (prompt_len,) int32
    greq: np.ndarray         # (guide_request_len,) int32
    window: bool


@dataclasses.dataclass
class Traffic:
    warmup: list[Request]
    window: list[Request]
    known_kind: np.ndarray       # (K,) index into KINDS
    known_guides: np.ndarray     # (K, guide_len) int32, zero where bare/hard
    planted_prompts: np.ndarray  # (K, prompt_len): one earlier question each
    calib_prompts: np.ndarray    # (n_calib, prompt_len): a second question


def _seed_words(seed: int) -> list[int]:
    s = int(seed) % (1 << 64)
    return [s >> 32, s & 0xFFFFFFFF]


class Generator:
    """Content of one seed under one mix."""

    def __init__(self, mix: dict, seed: int, guide_len: int):
        self.mix = mix
        self.words = _seed_words(seed)
        self.guide_len = guide_len
        self.L = int(mix["prompt_len"])
        self.P = int(mix["skill_part_len"])
        self.first = int(mix["first_content_token"])
        self.vocab = int(mix["vocab"])
        fixed = np.random.default_rng([0x6E51, 16])
        self.request_block = np.concatenate([
            [GUIDE_REQ], fixed.integers(
                self.first, self.vocab,
                size=int(mix["guide_request_len"])
                - int(mix["guide_request_prefix"]) - 1)]).astype(np.int32)

    def rng(self, *tag: int) -> np.random.Generator:
        return np.random.default_rng(self.words + list(tag))

    def skill_part(self, skill: int) -> np.ndarray:
        r = self.rng(1, skill)
        width = int(self.mix["skill_slice"])
        start = int(r.integers(self.first, self.vocab - width))
        return r.integers(start, start + width, size=self.P - 1)

    def prompt(self, skill: int, *qtag: int) -> np.ndarray:
        q = self.rng(2, *qtag).integers(self.first, self.vocab,
                                        size=self.L - self.P - 1)
        return np.concatenate([[BOS], self.skill_part(skill), q,
                               [ANS]]).astype(np.int32)

    def greq(self, prompt: np.ndarray) -> np.ndarray:
        head = prompt[:int(self.mix["guide_request_prefix"])]
        return np.concatenate([head, self.request_block]).astype(np.int32)


def _arrivals(mix: dict, n: int, rate: float, seconds: float,
              salt: int) -> np.ndarray:
    """``n`` arrival instants in [0, seconds): the mix's fixed process,
    scaled so the last arrival falls half a mean gap before the end."""
    spec = dict(mix["arrivals"])
    process = loadgen.PROCESSES[spec.pop("process")]
    fixed_seed = spec.pop("seed") + salt
    t = np.asarray([e.t for e in process(n, rate, seed=fixed_seed, **spec)])
    return t * (seconds - 0.5 / rate) / t[-1]


def generate(mix: dict, rate: float, seconds: float, seed: int,
             guide_len: int) -> Traffic:
    g = Generator(mix, seed, guide_len)
    K = int(mix["known_skills"])
    fixed = np.random.default_rng([int(mix["arrivals"]["seed"]), K])
    order = g.rng(3)

    # known skills: the kind at each popularity rank is fixed by the mix;
    # the seed picks which skill id holds each rank
    shares = [float(mix["known_kinds"][k]) for k in KINDS]
    counts = [int(round(s * K)) for s in shares[:-1]]
    counts.append(K - sum(counts))
    kind_of_rank = fixed.permutation(
        np.repeat(np.arange(len(KINDS)), counts))
    rank_to_skill = order.permutation(K)
    known_kind = np.empty(K, np.int64)
    known_kind[rank_to_skill] = kind_of_rank
    known_guides = np.zeros((K, guide_len), np.int32)
    with_guide = np.flatnonzero(known_kind == KINDS.index("guide"))
    known_guides[with_guide, 0] = GUIDE_START
    known_guides[with_guide, 1:3] = g.rng(4).integers(
        g.first, g.vocab, size=(len(with_guide), 2))
    known_guides[with_guide, 3] = GUIDE_END
    zipf_p = 1.0 / np.arange(1, K + 1) ** float(mix["zipf_s"])
    zipf_p /= zipf_p.sum()

    n_win = max(1, int(round(rate * seconds)))
    n_warm = max(1, int(round(rate * float(mix["warmup_s"]))))
    new_ids = K + order.choice(int(mix["new_skill_pool"]),
                               size=n_win + n_warm, replace=False)
    new_iter = iter(new_ids.tolist())

    def stretch(n: int, length: float, first_rid: int, window: bool):
        n_known = int(round(float(mix["known_share"]) * n))
        ranks = fixed.choice(K, size=n_known, p=zipf_p)
        known = iter(rank_to_skill[ranks].tolist())
        is_known = fixed.permutation(np.arange(n) < n_known)
        times = _arrivals(mix, n, rate, length, int(window))
        out = []
        for i in range(n):
            rid = first_rid + i
            skill = next(known) if is_known[i] else next(new_iter)
            kind = KINDS[known_kind[skill]] if is_known[i] else "new"
            p = g.prompt(skill, 0, rid)
            out.append(Request(rid=rid, t=float(times[i]), skill=int(skill),
                               kind=kind, prompt=p, greq=g.greq(p),
                               window=window))
        return out

    warm = stretch(n_warm, float(mix["warmup_s"]), 0, False)
    win = stretch(n_win, seconds, n_warm, True)
    planted = np.stack([g.prompt(s, 1, s) for s in range(K)])
    n_cal = min(K, int(mix["calibration_skills"]))
    calib = np.stack([g.prompt(s, 2, s) for s in range(n_cal)])
    return Traffic(warmup=warm, window=win, known_kind=known_kind,
                   known_guides=known_guides, planted_prompts=planted,
                   calib_prompts=calib)

"""The one traffic generator: reads a mix's parameters and makes a run's
requests from ``--seed``.

A mix (``bench/traffic/<mix>.json``) gives the prompt and output length
distributions, the task mix and the arrival process; a cell
(``bench/cells/<cell>.json``) gives the offered mean rate.  The population
of requests — lengths, query texts, labels and the inter-arrival gaps — is
drawn once from the mix's ``population_seed`` for the run's size (mean rate
times window seconds).  ``--seed`` then permutes the requests, rotates the
gap sequence and draws the prompt token ids.  So every seed offers the same
set of sizes and arrivals, in another order, and two runs of one seed offer
the same inputs.

The query/label model is a copy of ``repro.data.qaserve.generate`` (the
paper's 37/7/19/37 task mix, lognormal lengths); the arrival processes are
copies of ``repro.data.arrivals`` with the MMPP parameterised by its mean
rate.  They are copied so that a change to the program cannot move the
yardstick.
"""
from __future__ import annotations

import dataclasses
from typing import List

import numpy as np

TASKS = ("mmlu", "gpqa", "math500", "gsm8k")
_TOPIC_D = 8
_WORDBANK = {
    "mmlu": ("which enzyme gene protein oncogene receptor pathway catalyzes "
             "member following encoded answer choose option biology history "
             "law economics psychology philosophy anatomy").split(),
    "gpqa": ("graduate quantum spectroscopy hamiltonian orbital symmetry "
             "reaction stereochemistry relativistic decay cross section "
             "perturbation eigenstate degenerate").split(),
    "math500": ("prove integral polynomial roots converge series modulo prime "
                "triangle circle inscribed maximize derivative matrix "
                "determinant combinatorial").split(),
    "gsm8k": ("apples dollars minutes total each buys sells speed train "
              "remaining shares half twice children marbles costs per week "
              "how many left").split(),
}
_TASK_DIFF_MU = {"mmlu": 0.0, "gpqa": 1.6, "math500": 1.1, "gsm8k": -0.4}
_TASK_LEN_MU = {"mmlu": -0.4, "gpqa": 0.4, "math500": 0.5, "gsm8k": 0.1}


@dataclasses.dataclass
class Population:
    """N queries x M pool columns, as ``repro.data.qaserve.generate`` makes
    them (query text, task, latent labels, true output lengths)."""
    queries: List[str]
    task: np.ndarray
    difficulty: np.ndarray
    input_len: np.ndarray
    correct: np.ndarray       # (N, M) int8
    out_len: np.ndarray       # (N, M) int
    topic: np.ndarray

    def take(self, idx) -> "Population":
        idx = np.asarray(idx)
        return Population([self.queries[i] for i in idx], self.task[idx],
                          self.difficulty[idx], self.input_len[idx],
                          self.correct[idx], self.out_len[idx],
                          self.topic[idx])


def qaserve_population(n: int, seed: int, pool: list, *, task_p,
                       prompt: dict, out_cap: int = 1024) -> Population:
    """Copy of ``repro.data.qaserve.generate`` with the task mix and the
    prompt-length law taken as parameters.  ``pool`` rows carry ``skill``
    and ``verbosity``."""
    rng = np.random.RandomState(seed)
    m = len(pool)
    task_ids = rng.choice(len(TASKS), size=n, p=np.asarray(task_p) /
                          np.sum(task_p))
    topic = rng.randn(n, _TOPIC_D) * 0.5
    affinity = np.random.RandomState(seed + 1).randn(m, _TOPIC_D) * 0.4
    difficulty = np.array([_TASK_DIFF_MU[TASKS[t]] + 0.9 * rng.randn()
                           for t in task_ids])
    input_len = np.clip(rng.lognormal(prompt["mu"], prompt["sigma"], size=n),
                        prompt["lo"], prompt["hi"]).astype(int)
    queries = []
    for i in range(n):
        words = _WORDBANK[TASKS[task_ids[i]]]
        k = int(np.clip(input_len[i] // 8, 4, 24))
        base = " ".join(rng.choice(words, size=k))
        marks = [f"t{d}{'p' if topic[i, d] > 0 else 'n'}"
                 for d in range(_TOPIC_D) if abs(topic[i, d]) > 0.35]
        dlevel = int(np.clip((difficulty[i] + 2) * 2, 0, 7))
        queries.append(f"{base} {' '.join(marks)} d{dlevel} q{i}")
    skills = np.array([p["skill"] for p in pool])
    logits = 3.0 * (skills[None, :] - difficulty[:, None]) + topic @ affinity.T
    correct = (rng.rand(n, m) < 1.0 / (1.0 + np.exp(-logits))).astype(np.int8)
    mu = np.array([[p["verbosity"] + _TASK_LEN_MU[TASKS[t]] for p in pool]
                   for t in task_ids])
    out_len = np.clip(rng.lognormal(mu, 0.45), 8, out_cap).astype(int)
    return Population(queries, task_ids, difficulty, input_len, correct,
                      out_len, topic)


def poisson_gaps(n: int, rng: np.random.RandomState) -> np.ndarray:
    """Exponential inter-arrival gaps of mean 1 (``arrivals.poisson``)."""
    return rng.exponential(1.0, size=n)


def mmpp_gaps(n: int, rng: np.random.RandomState, *, burst: float,
              p_switch: float) -> np.ndarray:
    """Two-state MMPP (``arrivals.bursty``): a hot state at ``burst`` times
    and a quiet one at ``1/burst`` times a base rate, switching with
    probability ``p_switch`` after each arrival.  The caller rescales the
    gaps to the mean rate it wants, so the base rate is not a parameter."""
    hot = rng.rand() < 0.5
    gaps = np.empty(n)
    for i in range(n):
        gaps[i] = rng.exponential(1.0 / (burst if hot else 1.0 / burst))
        if rng.rand() < p_switch:
            hot = not hot
    return gaps


@dataclasses.dataclass
class Schedule:
    """One run's requests in due order."""
    due: np.ndarray            # (N,) seconds after the window opens
    prompts: List[np.ndarray]  # token ids; the last one feeds decode step 1
    max_new: np.ndarray        # (N,) int
    pop: Population            # route features and labels, same order

    @property
    def n(self) -> int:
        return len(self.due)

    def buckets(self, page: int) -> List[int]:
        """Prefill buckets the engine will use: prompt length less the fed
        token, rounded up to a page (``Endpoint._bucket``)."""
        return sorted({-(-(len(p) - 1) // page) * page
                       for p in self.prompts})


def _lognormal_int(rng, law: dict, n: int) -> np.ndarray:
    return np.clip(rng.lognormal(law["mu"], law["sigma"], size=n),
                   law["lo"], law["hi"]).astype(int)


def make_schedule(mix: dict, *, pool: list, main_column: int, rate: float,
                  seconds: float, seed: int, vocab: int) -> Schedule:
    """The run's open-loop schedule: ``round(rate * seconds)`` requests due
    inside ``[0, seconds)``, their gaps rescaled to that mean rate."""
    n = max(1, int(round(rate * seconds)))
    pseed = int(mix["population_seed"])
    pop = qaserve_population(n, pseed, pool, task_p=mix["task_p"],
                             prompt=mix["prompt_len"])
    prng = np.random.RandomState(pseed + 2)
    out = mix["output_len"]
    if out == "main_column":
        max_new = pop.out_len[:, main_column].copy()
    else:
        max_new = _lognormal_int(prng, out, n)
        # the route's length/cost labels follow the served length
        pop.out_len[:, main_column] = max_new
    arr = mix["arrivals"]
    if arr["kind"] == "poisson":
        gaps = poisson_gaps(n, prng)
    elif arr["kind"] == "mmpp":
        gaps = mmpp_gaps(n, prng, burst=arr["burst"],
                         p_switch=arr["p_switch"])
    else:
        raise ValueError(f"unknown arrival kind {arr['kind']!r}")
    gaps = gaps * (seconds / gaps.sum())          # n arrivals fill the window

    rs = np.random.RandomState(np.random.SeedSequence(
        [int(seed) & 0xFFFFFFFF, int(seed) >> 32, 1]).generate_state(1)[0])
    perm = rs.permutation(n)
    shift = int(rs.randint(n))
    gaps = np.roll(gaps, shift)   # rotation keeps the MMPP's bursts whole
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    pop = pop.take(perm)
    max_new = max_new[perm]
    prompts = [rs.randint(2, vocab, size=int(pop.input_len[i]))
               .astype(np.int32) for i in range(n)]
    return Schedule(due=due, prompts=prompts, max_new=max_new, pop=pop)


def router_history(mix: dict, *, pool: list, n: int) -> Population:
    """The router's fitted history (its retrieval store), fixed for the mix:
    a deployment's store does not change with the run's seed."""
    return qaserve_population(n, int(mix["population_seed"]) + 100, pool,
                              task_p=mix["task_p"], prompt=mix["prompt_len"])

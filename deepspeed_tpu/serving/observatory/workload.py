"""Seeded, fully deterministic open-loop workload generation.

Every serving number this repo had before this module came from CLOSED
loops: N clients, each submitting its next request the moment the
previous one completes.  A closed loop self-throttles — the arrival
rate falls to whatever the server sustains — so it can never show
queueing collapse, which is the regime a production fleet under
millions of users actually lives in.  The DistServe/FastGen evaluation
methodology (the reference analogs' benchmarking discipline) is
OPEN-loop: requests arrive on a schedule drawn from an arrival process,
independent of completions, and the measured quantity is how latency /
goodput degrade as the offered load ρ approaches and passes 1.

`WorkloadGenerator` draws that schedule deterministically: one seeded
`numpy.random.RandomState`, a fixed draw order, and explicit arrival /
length distributions, so the same seed replays the same workload
bit-for-bit (locked by test) and a sweep's "ρ = 1.3 arm" means the
same thing on every run.

Arrival processes:

- ``poisson``        exponential inter-arrivals at `rate_rps` (the
                     M/*/c default — memoryless arrivals are the
                     classical open-loop stress shape)
- ``deterministic``  fixed `1/rate_rps` spacing (D arrivals: isolates
                     queueing from arrival burstiness)
- ``burst``          groups of `burst_size` simultaneous arrivals,
                     groups spaced so the LONG-RUN rate is still
                     `rate_rps` (the thundering-herd shape: same mean
                     load, much deeper transient queues)

Lengths are heavy-tailed by default (clipped lognormal — most prompts
short, a fat tail of huge ones, the shape real serving traffic has),
with optional shared-prefix and priority mixes.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

__all__ = ["WorkloadItem", "WorkloadGenerator", "ARRIVAL_PROCESSES"]

ARRIVAL_PROCESSES = ("poisson", "deterministic", "burst")


@dataclass
class WorkloadItem:
    """One scheduled request: arrives at `arrival_s` (virtual seconds
    from workload start) regardless of what the server is doing."""

    index: int
    arrival_s: float
    prompt: np.ndarray
    max_new_tokens: int
    priority: int = 0
    shared_prefix: bool = False
    # multi-tenant dimension (num_tenants > 0): which tenant submitted
    # this request, and the tenant's LoRA adapter when the draw says
    # the request exercises one.  Defaults are the single-tenant
    # parity values ServeLoop.submit defaults to.
    tenant: str = "default"
    adapter_id: Optional[str] = None
    # structured dimension (structured_frac > 0): the output grammar
    # this request decodes under (a serving/structured ResponseFormat),
    # None = unconstrained — the parity default ServeLoop.submit uses
    response_format: Optional[object] = None

    def total_tokens(self) -> int:
        return len(self.prompt) + self.max_new_tokens


class WorkloadGenerator:
    """Deterministic open-loop workload: arrival schedule + prompts.

    All randomness derives from the ONE constructor seed, fanned into
    an independent child stream per quantity (arrivals, prompt
    lengths, output lengths, prefix membership, priorities, prompt
    tokens).  `generate(n)` is therefore a pure function of the
    constructor arguments — the determinism contract a sweep's arms
    lean on — and the streams are
    PREFIX-stable: `generate(m)[:n] == generate(n)` for m >= n (a
    longer run extends the schedule; with one shared stream the later
    draws' offsets would depend on n and every prompt would reshuffle).

    Length distributions (`length_dist`):

    - ``lognormal``  exp(N(log(mean) - sigma^2/2, sigma)) clipped to
                     [min, max] — heavy-tailed, mean ~= `mean` before
                     clipping
    - ``fixed``      every draw = `mean` (calibration workloads)
    """

    def __init__(self, vocab_size: int, seed: int = 0,
                 arrival: str = "poisson", rate_rps: float = 1.0,
                 burst_size: int = 8,
                 length_dist: str = "lognormal",
                 prompt_len_mean: float = 96.0,
                 prompt_len_sigma: float = 0.8,
                 prompt_len_min: int = 4, prompt_len_max: int = 512,
                 output_len_mean: float = 24.0,
                 output_len_sigma: float = 0.6,
                 output_len_min: int = 2, output_len_max: int = 128,
                 shared_prefix_len: int = 0,
                 shared_prefix_frac: float = 0.0,
                 priority_mix: Optional[Dict[int, float]] = None,
                 num_tenants: int = 0,
                 tenant_zipf_a: float = 1.0,
                 adapter_frac: float = 0.0,
                 structured_frac: float = 0.0,
                 structured_formats: Optional[List] = None):
        if arrival not in ARRIVAL_PROCESSES:
            raise ValueError(
                f"arrival must be one of {ARRIVAL_PROCESSES}, got "
                f"{arrival!r}")
        if length_dist not in ("lognormal", "fixed"):
            raise ValueError(
                f"length_dist must be 'lognormal' or 'fixed', got "
                f"{length_dist!r}")
        if rate_rps <= 0:
            raise ValueError(f"rate_rps must be > 0, got {rate_rps}")
        if burst_size < 1:
            raise ValueError(f"burst_size must be >= 1, got {burst_size}")
        if not 0.0 <= shared_prefix_frac <= 1.0:
            raise ValueError(
                f"shared_prefix_frac must be in [0, 1], got "
                f"{shared_prefix_frac}")
        if shared_prefix_frac > 0.0 and shared_prefix_len < 1:
            raise ValueError(
                "shared_prefix_frac > 0 needs shared_prefix_len >= 1")
        if shared_prefix_frac > 0.0 and shared_prefix_len >= prompt_len_max:
            # the prefix counts TOWARD the drawn prompt length (the
            # declared prompt_len_max is a real bound an engine can be
            # sized from), so it must leave room for >= 1 tail token
            raise ValueError(
                f"shared_prefix_len={shared_prefix_len} must be < "
                f"prompt_len_max={prompt_len_max}: the shared prefix "
                f"counts toward the drawn prompt length")
        if priority_mix is not None:
            if not priority_mix or any(w < 0 for w in
                                       priority_mix.values()) \
                    or sum(priority_mix.values()) <= 0:
                raise ValueError(
                    f"priority_mix needs positive total weight, got "
                    f"{priority_mix}")
        self.vocab_size = int(vocab_size)
        self.seed = int(seed)
        self.arrival = arrival
        self.rate_rps = float(rate_rps)
        self.burst_size = int(burst_size)
        self.length_dist = length_dist
        self.prompt_len = (float(prompt_len_mean),
                           float(prompt_len_sigma),
                           int(prompt_len_min), int(prompt_len_max))
        self.output_len = (float(output_len_mean),
                           float(output_len_sigma),
                           int(output_len_min), int(output_len_max))
        if num_tenants < 0:
            raise ValueError(f"num_tenants must be >= 0, got "
                             f"{num_tenants}")
        if tenant_zipf_a < 0.0:
            raise ValueError(f"tenant_zipf_a must be >= 0, got "
                             f"{tenant_zipf_a}")
        if not 0.0 <= adapter_frac <= 1.0:
            raise ValueError(f"adapter_frac must be in [0, 1], got "
                             f"{adapter_frac}")
        if adapter_frac > 0.0 and num_tenants < 1:
            raise ValueError(
                "adapter_frac > 0 needs num_tenants >= 1: adapters are "
                "per-tenant, there is no adapter to draw without one")
        self.shared_prefix_len = int(shared_prefix_len)
        self.shared_prefix_frac = float(shared_prefix_frac)
        self.priority_mix = dict(priority_mix) if priority_mix else None
        # multi-tenant dimension: 0 = off (every item is the default
        # tenant, no adapters — bit-for-bit the pre-tenancy schedule).
        # Tenant popularity is Zipfian: tenant k gets weight
        # 1/(k+1)^a, so t0 dominates (the few-hot-tenants shape real
        # multi-tenant traffic has); a=0 is uniform.
        self.num_tenants = int(num_tenants)
        self.tenant_zipf_a = float(tenant_zipf_a)
        self.adapter_frac = float(adapter_frac)
        # structured dimension: structured_frac of the items decode
        # under a grammar drawn (seeded, prefix-stable) from the
        # caller-supplied format mix; 0 = off — byte-identical items
        # (locked by test: the extra child seed is drawn from the same
        # sequential bitstream, and no per-item stream is consumed)
        if not 0.0 <= structured_frac <= 1.0:
            raise ValueError(f"structured_frac must be in [0, 1], got "
                             f"{structured_frac}")
        if structured_frac > 0.0 and not structured_formats:
            raise ValueError(
                "structured_frac > 0 needs structured_formats: there is "
                "no grammar to draw from (pass serving.structured "
                "ResponseFormat objects)")
        self.structured_frac = float(structured_frac)
        self.structured_formats = (list(structured_formats)
                                   if structured_formats else None)

    # -- draws ------------------------------------------------------------
    def _arrivals(self, rng: np.random.RandomState, n: int) -> np.ndarray:
        if self.arrival == "deterministic":
            gaps = np.full(n, 1.0 / self.rate_rps)
        elif self.arrival == "poisson":
            gaps = rng.exponential(1.0 / self.rate_rps, size=n)
        else:                                   # burst
            # groups of burst_size arrive together; group spacing keeps
            # the long-run rate at rate_rps
            gaps = np.zeros(n)
            gaps[::self.burst_size] = self.burst_size / self.rate_rps
            gaps[0] = 0.0
        return np.cumsum(gaps)

    def _lengths(self, rng: np.random.RandomState, n: int,
                 spec: Tuple[float, float, int, int]) -> np.ndarray:
        mean, sigma, lo, hi = spec
        if self.length_dist == "fixed":
            return np.full(n, int(round(mean)), np.int64)
        # mean-preserving lognormal before clipping: mu = log(mean) -
        # sigma^2/2 makes E[exp(N(mu, sigma))] = mean
        mu = np.log(mean) - sigma * sigma / 2.0
        draw = rng.lognormal(mu, sigma, size=n)
        return np.clip(np.rint(draw), lo, hi).astype(np.int64)

    def generate(self, n: int) -> List[WorkloadItem]:
        """The first `n` scheduled requests.  Deterministic AND
        prefix-stable: `generate(m)[:n]` equals `generate(n)` item for
        item whenever m >= n — a longer run extends the schedule, it
        never reshuffles a shorter one (locked by test)."""
        if n < 1:
            raise ValueError(f"n must be >= 1, got {n}")
        # one child RandomState per quantity: numpy's vectorized draws
        # consume a stream sequentially, so per-stream the first n
        # values never depend on how many more are drawn — which is
        # what makes generate() prefix-stable in n
        # size=8 extends the pre-tenancy size=6 / pre-structured size=7
        # fan-out: randint fills the array from one sequential
        # bitstream, so the earlier child seeds — and with
        # num_tenants=0 / structured_frac=0 every draw below — stay
        # bit-for-bit the old schedule (parity, locked by test)
        child = np.random.RandomState(self.seed).randint(
            0, 2**31 - 1, size=8)
        (rng_arr, rng_plen, rng_olen,
         rng_mask, rng_pri, rng_tok,
         rng_tenant, rng_fmt) = (np.random.RandomState(s) for s in child)
        arrivals = self._arrivals(rng_arr, n)
        prompt_lens = self._lengths(rng_plen, n, self.prompt_len)
        output_lens = self._lengths(rng_olen, n, self.output_len)
        shared = (rng_tok.randint(0, self.vocab_size,
                                  self.shared_prefix_len)
                  .astype(np.int32)
                  if self.shared_prefix_len > 0 else None)
        shared_mask = (rng_mask.uniform(size=n) < self.shared_prefix_frac
                       if shared is not None else np.zeros(n, bool))
        tenants: Optional[np.ndarray] = None
        adapter_mask = np.zeros(n, bool)
        tenant_prefixes: List[np.ndarray] = []
        if self.num_tenants > 0:
            # fixed-size draws FIRST (per-tenant prefix tokens depend
            # only on constructor args), then ONE (n, 2) uniform sweep
            # filled row-major — item i reads offsets 2i, 2i+1, so the
            # tenant stream stays prefix-stable in n like every other
            if shared is not None:
                tenant_prefixes = [
                    rng_tenant.randint(0, self.vocab_size,
                                       self.shared_prefix_len)
                    .astype(np.int32)
                    for _ in range(self.num_tenants)]
            w = 1.0 / np.arange(1, self.num_tenants + 1,
                                dtype=np.float64) ** self.tenant_zipf_a
            cum = np.cumsum(w / w.sum())
            u = rng_tenant.uniform(size=(n, 2))
            tenants = np.searchsorted(cum, u[:, 0], side="right")
            tenants = np.minimum(tenants, self.num_tenants - 1)
            adapter_mask = u[:, 1] < self.adapter_frac
        fmt_pick: Optional[np.ndarray] = None
        fmt_mask = np.zeros(n, bool)
        if self.structured_frac > 0.0:
            # one (n, 2) sweep filled row-major, like the tenant draw:
            # membership and format choice per item read fixed offsets,
            # keeping the structured stream prefix-stable in n
            u = rng_fmt.uniform(size=(n, 2))
            fmt_mask = u[:, 0] < self.structured_frac
            fmt_pick = np.minimum(
                (u[:, 1] * len(self.structured_formats)).astype(np.int64),
                len(self.structured_formats) - 1)
        if self.priority_mix is not None:
            prios = sorted(self.priority_mix)
            w = np.asarray([self.priority_mix[p] for p in prios],
                           np.float64)
            pri_draw = rng_pri.choice(len(prios), size=n, p=w / w.sum())
        items: List[WorkloadItem] = []
        for i in range(n):
            # token draws run per item in index order off their own
            # stream: item i's tokens depend only on items 0..i-1's
            # (prefix-stable) lengths, never on n
            n_p = int(prompt_lens[i])
            tid = int(tenants[i]) if tenants is not None else None
            if shared is not None and shared_mask[i]:
                # the prefix counts toward the drawn length: total
                # prompt size stays inside the declared
                # [prompt_len_min(+prefix), prompt_len_max] bound an
                # engine gets sized from.  Under tenancy the item
                # reuses ITS TENANT's prefix — cross-tenant prompts
                # share nothing, so the radix cache's sharing follows
                # the tenant axis (what a fleet's prefix routing sees)
                pfx = shared if tid is None else tenant_prefixes[tid]
                tail_len = max(1, n_p - self.shared_prefix_len)
                tail = rng_tok.randint(0, self.vocab_size,
                                       tail_len).astype(np.int32)
                prompt = np.concatenate([pfx, tail])
            else:
                prompt = rng_tok.randint(0, self.vocab_size,
                                         max(1, n_p)).astype(np.int32)
            tenant = "default" if tid is None else f"t{tid}"
            items.append(WorkloadItem(
                index=i,
                arrival_s=float(arrivals[i]),
                prompt=prompt,
                max_new_tokens=int(output_lens[i]),
                priority=(prios[pri_draw[i]]
                          if self.priority_mix is not None else 0),
                shared_prefix=bool(shared_mask[i]),
                tenant=tenant,
                adapter_id=(f"lora_{tenant}" if adapter_mask[i]
                            else None),
                response_format=(
                    self.structured_formats[int(fmt_pick[i])]
                    if fmt_mask[i] else None)))
        return items

    def describe(self) -> Dict[str, Any]:
        """The generator's full parameterization, so that a result can
        name the workload it ran."""
        return {
            "seed": self.seed, "arrival": self.arrival,
            "rate_rps": self.rate_rps, "burst_size": self.burst_size,
            "length_dist": self.length_dist,
            "prompt_len": list(self.prompt_len),
            "output_len": list(self.output_len),
            "shared_prefix_len": self.shared_prefix_len,
            "shared_prefix_frac": self.shared_prefix_frac,
            "priority_mix": self.priority_mix,
            "num_tenants": self.num_tenants,
            "tenant_zipf_a": self.tenant_zipf_a,
            "adapter_frac": self.adapter_frac,
            "structured_frac": self.structured_frac,
            # (kind, spec) pairs, not objects: the description must
            # serialize as JSON
            "structured_formats": (
                [(f.kind, f.spec) for f in self.structured_formats]
                if self.structured_formats else None),
        }

    def with_rate(self, rate_rps: float) -> "WorkloadGenerator":
        """A copy at a different offered rate, all else identical —
        the sweep's ρ knob.  NOTE: the copy re-seeds from the same
        seed, so prompts/lengths are identical across arms; only the
        arrival spacing changes."""
        g = WorkloadGenerator.__new__(WorkloadGenerator)
        g.__dict__.update(self.__dict__)
        g.rate_rps = float(rate_rps)
        return g

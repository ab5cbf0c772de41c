"""Plain reference of the batch SJF-BCO re-plan (arXiv:2207.07817, Alg. 1-3).

Written from the paper and the semantics the scheduler states, and
importing nothing of it.  One (theta, kappa) attempt at a time, one job
at a time, one candidate at a time: the sequential bisection on the
busy-time budget theta over [1, horizon], for each theta every kappa in
ascending order, each attempt placing the jobs in (G_j, id) order with
FA-FFP (G_j <= kappa) or LBSGF (G_j > kappa), each placement refined by
the Eq. (6)-(8) contention estimate against the jobs already placed and
retried up to four times under the Eq. (16) budget.

``dtype`` is the precision of every quantity.  The configuration states
float64 (the scheduler's decisions must equal the float64 algorithm's);
float32 is the control that a comparison must reject.  Homogeneous
clusters only (the configurations this benchmark runs).
"""
from __future__ import annotations

import math
from types import SimpleNamespace

import numpy as np

TIE = 1e-9          # the Eq. (16) budget slack and the overlap cut
TRIES = 4           # refine-and-retry rounds per placement
ESCALATE = 1.05     # nominal-estimate escalation between rounds


class Ref:
    """Cluster constants and the per-job terms, in one precision."""

    def __init__(self, cluster: dict, u: float, dtype=np.float64):
        self.f = f = np.dtype(dtype).type
        self.caps = np.asarray(cluster["capacities"], dtype=np.int64)
        self.S = len(self.caps)
        self.srv = np.repeat(np.arange(self.S), self.caps)
        self.N = len(self.srv)
        for key in ("b_intra", "b_inter", "gpu_speed", "xi1", "xi2", "alpha"):
            setattr(self, key, f(cluster[key]))
        self.u = f(u)
        self.k_max = max(f(1.0), self.xi1 * f(int(self.caps.max())))

    # -- Eq. (8) terms -----------------------------------------------------
    def _share(self, job) -> object:
        f = self.f
        w = f(job.num_gpus)
        return (f(job.grad_size) / w) * (w - f(1.0)) if job.num_gpus > 1 \
            else f(0.0)

    def _compute(self, job) -> object:
        f = self.f
        return f(job.dt_fwd) * f(job.batch) + f(job.dt_bwd)

    def tau(self, job, p: int, n_srv: int) -> object:
        """Eq. (8): exchange + reduction + overhead + compute."""
        f = self.f
        share = self._share(job)
        k = max(self.xi1 * f(p), f(1.0))
        if n_srv > 1:
            bandwidth = self.b_inter / (k + self.alpha * (k - f(1.0)))
        else:
            bandwidth = self.b_intra
        return (f(2.0) * share / bandwidth + share / self.gpu_speed
                + self.xi2 * f(n_srv) + self._compute(job))

    def slots(self, iters: int, tau) -> object:
        """Slots for ``iters`` iterations at ``floor(1/tau)`` a slot."""
        phi = max(1, math.floor(self.f(1.0) / tau))
        return self.f(math.ceil(iters / phi))

    def nominal_rho(self, job) -> object:
        """The contention-free lower estimate: one server, full b_intra."""
        f = self.f
        share = self._share(job)
        tau_lo = (f(2.0) * share / self.b_intra + share / self.gpu_speed
                  + self.xi2 * f(1.0) + self._compute(job))
        return self.slots(job.iters, tau_lo)


class Attempt:
    """Busy clocks U, real clocks R and the placed jobs of one attempt."""

    def __init__(self, ref: Ref):
        self.ref = ref
        self.U = np.zeros(ref.N, dtype=ref.f)
        self.R = np.zeros(ref.N, dtype=ref.f)
        self.fins: list[list] = [[] for _ in range(ref.S)]   # straddlers
        self.assignment: list[tuple[int, np.ndarray]] = []
        self.start: dict[int, object] = {}
        self.finish: dict[int, object] = {}

    def pool(self, rho_nom, theta) -> np.ndarray:
        """GPUs whose budget holds the job at estimate ``rho_nom``."""
        r = self.ref
        return np.flatnonzero(self.U + rho_nom / r.u <= theta + r.f(TIE))

    def fa_ffp(self, job, rho_nom, theta):
        """Alg. 2: best-fit the whole job into one server (fewest feasible
        GPUs left, then most loaded, then lowest id); else the globally
        least-busy feasible GPUs."""
        r, U, G = self.ref, self.U, job.num_gpus
        feasible = self.pool(rho_nom, theta)
        if len(feasible) < G:
            return None
        count = np.bincount(r.srv[feasible], minlength=r.S)
        fits = [s for s in range(r.S) if count[s] >= G]
        if fits:
            load = np.bincount(r.srv, weights=U, minlength=r.S).astype(r.f)
            best = min(fits, key=lambda s: (count[s] - G, -load[s], s))
            cand = [g for g in feasible if r.srv[g] == best]
        else:
            cand = list(feasible)
        return np.asarray(sorted(cand, key=lambda g: (U[g], g))[:G])

    def lbsgf(self, job, rho_nom, theta):
        """Alg. 3: the least-busy servers (by mean busy time) that hold
        lambda_j G_j GPUs, their feasible GPUs server by server, least
        busy first."""
        r, U, G = self.ref, self.U, job.num_gpus
        load = np.bincount(r.srv, weights=U, minlength=r.S).astype(r.f)
        mean = load / r.caps.astype(r.f)
        order = sorted(range(r.S), key=lambda s: (mean[s], s))
        need, held, chosen = job.lam * G, 0, []
        for s in order:
            chosen.append(s)
            held += int(r.caps[s])
            if held >= need:
                break
        rank = {s: i for i, s in enumerate(chosen)}
        cand = [g for g in self.pool(rho_nom, theta) if r.srv[g] in rank]
        if len(cand) < G:
            return None
        return np.asarray(sorted(cand, key=lambda g: (rank[r.srv[g]], U[g],
                                                      g))[:G])

    def refine(self, job, gpus):
        """rho_hat(y): Eq. (6) level against the placed straddlers still
        running at the gang start, Eq. (8), then slots."""
        r = self.ref
        start = self.R[gpus].max()
        y = np.bincount(r.srv[gpus], minlength=r.S)
        p = n_srv = 0
        for s in range(r.S):
            if y[s] > 0:
                n_srv += 1
                if y[s] < job.num_gpus:
                    alive = sum(1 for fin in self.fins[s]
                                if fin > start + r.f(TIE))
                    p = max(p, alive + 1)
        return r.slots(job.iters, r.tau(job, p, n_srv)), start

    def commit(self, job, gpus, rho, start) -> None:
        r = self.ref
        self.U[gpus] += rho / r.u
        self.R[gpus] = start + rho
        self.assignment.append((job.jid, gpus))
        self.start[job.jid] = start
        self.finish[job.jid] = start + rho
        y = np.bincount(r.srv[gpus], minlength=r.S)
        for s in range(r.S):
            if 0 < y[s] < job.num_gpus:
                self.fins[s].append(start + rho)

    def place(self, job, picker, rho_nom, theta) -> bool:
        """Pick, refine, re-check the budget; escalate and retry."""
        r = self.ref
        rho_try = rho_nom
        for _ in range(TRIES):
            gpus = picker(job, rho_try, theta)
            if gpus is None:
                return False
            rho, start = self.refine(job, gpus)
            if self.U[gpus].max() + rho / r.u <= theta + r.f(TIE):
                self.commit(job, gpus, rho, start)
                return True
            rho_try = max(rho, rho_try * r.f(ESCALATE))
        return False


def _attempt(ref: Ref, jobs_sorted, rho_nom, theta, kappa, n_jobs):
    att = Attempt(ref)
    for job in jobs_sorted:
        picker = att.fa_ffp if job.num_gpus <= kappa else att.lbsgf
        if not att.place(job, picker, rho_nom[job.jid], theta):
            return None
    est_start = np.full(n_jobs, -1.0)
    est_finish = np.full(n_jobs, -1.0)
    for jid, s in att.start.items():
        est_start[jid] = s
        est_finish[jid] = att.finish[jid]
    return SimpleNamespace(
        assignment=att.assignment, est_start=est_start,
        est_finish=est_finish, est_makespan=float(est_finish.max()),
        theta=float(theta), kappa=kappa,
        max_busy_time=float(att.U.max()))


def sjf_bco(cluster: dict, jobs, horizon: int, u: float,
            dtype=np.float64) -> SimpleNamespace:
    """The schedule Alg. 1 returns for ``jobs`` (ids 0..n-1 in order)."""
    ref = Ref(cluster, u, dtype)
    f = ref.f
    jobs_sorted = sorted(jobs, key=lambda j: (j.num_gpus, j.jid))
    rho_nom = {j.jid: ref.nominal_rho(j) for j in jobs}
    kappas = sorted({j.num_gpus for j in jobs} | {1})
    best = None
    left, right = f(1.0), f(horizon)
    while left <= right:
        theta = f(0.5) * (left + right)
        found = None
        for kappa in kappas:
            cand = _attempt(ref, jobs_sorted, rho_nom, theta, kappa,
                            len(jobs))
            if cand is not None and (found is None or
                                     cand.est_makespan < found.est_makespan):
                found = cand
        if found is not None:
            if best is None or found.est_makespan <= best.est_makespan:
                best = found
            right = theta - f(1.0)
        else:
            left = theta + f(1.0)
    if best is None:
        raise RuntimeError("no feasible schedule within the horizon")
    return best


FIELDS = ("assignment", "theta", "kappa", "est_start", "est_finish",
          "est_makespan", "max_busy_time")


def schedule_diff(got, ref) -> list[str]:
    """The fields of schedule ``got`` that differ from ``ref``, compared
    exactly (GPU id for GPU id, bit for bit)."""
    bad = []
    for name in FIELDS:
        a, b = getattr(got, name), getattr(ref, name)
        if name == "assignment":
            same = len(a) == len(b) and all(
                int(ja) == int(jb) and np.array_equal(np.asarray(ga),
                                                      np.asarray(gb))
                for (ja, ga), (jb, gb) in zip(a, b))
        elif name in ("est_start", "est_finish"):
            same = np.array_equal(np.asarray(a, dtype=np.float64),
                                  np.asarray(b, dtype=np.float64))
        else:
            same = a == b
        if not same:
            bad.append(name)
    return bad

"""Serving engines: LM decode batching + sketch-solve job admission.

Two serving surfaces share this module:

  * :class:`Engine` — the batched LM engine (flash prefill → step-synchronized
    batched decode over a fixed-shape KV cache).
  * :class:`SolveServer` — the *sketch-least-squares* front end: a job-admission
    API (:meth:`SolveServer.submit_solve`) that routes regression jobs through
    the async :class:`~repro.runtime.engine.ServerlessEngine` — streaming Welford
    averages, deadline→backoff→retry (adaptive deadlines optional), early stop,
    and a per-job telemetry summary — on any executor backend
    (``inline``/``thread``/``process``).

LM engine production notes encoded here (and exercised by tests):
  * prefill and decode are separate compilations — prefill cost is amortized once
    per request, decode is the steady-state loop;
  * the KV cache is allocated once at ``max_len`` and threaded functionally;
  * EOS handling is mask-based: finished rows keep decoding into a dead slot
    (fixed shapes beat ragged early-exit on TPU), outputs are trimmed on the host.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.models import lm

PyTree = object

# Host span around each admitted sketch-solve job (``jax.profiler.TraceAnnotation``);
# the job's task spans (``repro.runtime.tasks``) carry the same ``job`` id.
JOB_SPAN = "repro.serve.job"


def sample_token(key: jax.Array, logits: jax.Array, temperature: float = 0.0) -> jax.Array:
    """(B, V) logits -> (B,) token ids. temperature<=0 is greedy."""
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jax.random.categorical(key, logits / temperature, axis=-1).astype(jnp.int32)


@dataclasses.dataclass
class ServeConfig:
    max_batch: int = 8
    max_len: int = 256          # prompt + generation budget (cache allocation)
    temperature: float = 0.0
    eos_id: int = -1            # -1: never stop early
    seed: int = 0


class Engine:
    def __init__(self, cfg: ArchConfig, params: PyTree, sc: ServeConfig):
        self.cfg = cfg
        self.params = params
        self.sc = sc

        def _mask_pad(logits):
            # padded-vocab ids (Megatron-style table padding) must never be sampled
            if cfg.padded_vocab > cfg.vocab_size:
                neg = jnp.full((cfg.padded_vocab - cfg.vocab_size,), -1e30, logits.dtype)
                logits = logits.at[..., cfg.vocab_size :].set(neg)
            return logits

        self._mask_pad = _mask_pad

        def _prefill(params, batch):
            logits, cache = lm.batched_prefill(params, cfg, batch, cache_len=sc.max_len)
            return _mask_pad(logits), cache

        def _decode(params, tok, cache, pos, key):
            logits, cache = lm.decode_step(params, cfg, tok, cache, pos)
            logits = _mask_pad(logits)
            nxt = sample_token(key, logits, sc.temperature)
            return nxt, logits, cache

        self._prefill = jax.jit(_prefill)
        self._decode = jax.jit(_decode)

    # ------------------------------------------------------------------ API
    def generate(
        self,
        prompts: Sequence[Sequence[int]],
        *,
        max_new_tokens: int = 32,
        frames: Optional[jax.Array] = None,
        patches: Optional[jax.Array] = None,
    ) -> List[List[int]]:
        """Generate continuations for up to max_batch prompts (step-synchronized)."""
        out: List[List[int]] = []
        for i in range(0, len(prompts), self.sc.max_batch):
            chunk = prompts[i : i + self.sc.max_batch]
            out.extend(self._generate_batch(chunk, max_new_tokens, frames, patches))
        return out

    def _generate_batch(self, prompts, max_new_tokens, frames, patches) -> List[List[int]]:
        B = len(prompts)
        S = max(len(p) for p in prompts)
        assert S + max_new_tokens <= self.sc.max_len, "raise ServeConfig.max_len"
        # left-pad to a rectangle; padded prefix tokens are position-consistent but
        # their K/V are masked out of nothing — they are ordinary tokens the model
        # simply ignores at sampling time (standard fixed-shape serving trade-off).
        toks = np.zeros((B, S), np.int32)
        for r, p in enumerate(prompts):
            toks[r, S - len(p) :] = np.asarray(p, np.int32)
        batch = {"tokens": jnp.asarray(toks)}
        if patches is not None:
            batch["patches"] = patches[:B]
        if frames is not None:
            batch["frames"] = frames[:B]

        logits, cache = self._prefill(self.params, batch)
        key = jax.random.PRNGKey(self.sc.seed)
        tok = sample_token(key, logits, self.sc.temperature)
        generated = [tok]
        for t in range(1, max_new_tokens):
            key, sub = jax.random.split(key)
            tok, _, cache = self._decode(self.params, tok, cache, jnp.int32(S + t - 1), sub)
            generated.append(tok)
        gen = np.stack([np.asarray(g) for g in generated], axis=1)  # (B, T)
        outs: List[List[int]] = []
        for r in range(B):
            row = gen[r].tolist()
            if self.sc.eos_id >= 0 and self.sc.eos_id in row:
                row = row[: row.index(self.sc.eos_id) + 1]
            outs.append(row)
        return outs


# ===================================================================== solve serving


@dataclasses.dataclass
class SolveJob:
    """One admitted sketch-solve job: the result plus its full provenance."""

    job_id: int
    spec: object                 # sk.SketchSpec (kept untyped to avoid import cycle)
    q: int
    backend: str
    result: object               # repro.runtime.engine.RuntimeResult
    summary: Dict

    @property
    def xbar(self) -> np.ndarray:
        return self.result.xbar

    @property
    def realized_mask(self) -> np.ndarray:
        return self.result.realized_mask


class SolveServer:
    """Job admission for distributed sketch-least-squares (the paper's Algorithm 1
    as a *service*): every submitted job runs through the async
    :class:`~repro.runtime.engine.ServerlessEngine` — the same deadline → backoff
    → retry loop, streaming Welford averaging, and early stopping the benchmarks
    exercise — and leaves a per-job telemetry summary behind.

        from repro import runtime as rt
        from repro.serve import SolveServer

        server = SolveServer(
            latency=rt.HeavyTailLatency(scale_s=0.5, alpha=1.5, seed=0),
            config=rt.RuntimeConfig(deadline_s=1.0, max_retries=2),
            backend="process",                 # or "inline" / "thread"
            deadline=rt.AdaptiveDeadline(),    # optional: rolling-p95 deadlines
        )
        job = server.submit_solve(A, b, spec, q=32, error_fn="probe")
        job.xbar, job.summary                  # solution + telemetry
        server.telemetry()                     # aggregate across jobs

    The server is synchronous at the job level (submit_solve returns the finished
    job) while each job is internally asynchronous at the task level; per-job
    determinism is inherited from the engine (same seed ⇒ byte-identical event
    log on every backend).
    """

    def __init__(
        self,
        *,
        latency,
        config=None,
        backend: Union[str, object] = "thread",
        deadline=None,
    ):
        from repro.runtime.engine import RuntimeConfig

        self.latency = latency
        self.config = config or RuntimeConfig()
        self.backend = backend
        self.deadline = deadline
        self.jobs: List[SolveJob] = []

    # ------------------------------------------------------------------ admission

    def submit_solve(
        self,
        A: jax.Array,
        b: jax.Array,
        spec,
        q: int,
        *,
        key: Optional[jax.Array] = None,
        seed: int = 0,
        rounds: int = 1,
        reg: float = 0.0,
        method: str = "fused",
        error_fn: Union[None, str, Callable[[np.ndarray, int], float]] = None,
        probe_rows: int = 1024,
        least_norm: bool = False,
        save_events: Optional[str] = None,
    ) -> SolveJob:
        """Admit one job: ``rounds`` waves of ``q`` sketch-solve workers over
        (A, b) with sketch ``spec``, averaged as results arrive.

        ``error_fn``: ``"theory"`` / ``"probe"`` / callable / None (see
        :mod:`repro.runtime.tasks`); combined with ``config.target_error`` it
        enables early stop. ``least_norm=True`` routes the §V right-sketch worker
        (n < d). ``save_events`` dumps the job's JSONL event log to that path.
        """
        from repro.runtime import tasks as rt_tasks
        from repro.runtime.engine import ServerlessEngine

        job_id = len(self.jobs)
        with jax.profiler.TraceAnnotation(JOB_SPAN, job=job_id):
            if key is None:
                key = jax.random.PRNGKey(seed)
            if least_norm:
                compute = rt_tasks.make_least_norm_compute(spec, key, A, b)
            else:
                compute = rt_tasks.make_sketch_solve_compute(
                    spec, key, A, b, reg=reg, method=method
                )
            compute.job = job_id
            err = rt_tasks.resolve_error_fn(error_fn, spec, key, A, b, probe_rows=probe_rows)

            engine = ServerlessEngine(
                compute, self.latency, self.config,
                backend=self.backend, deadline=self.deadline,
            )
            task_list = [(w, r) for r in range(rounds) for w in range(q)]
            result = engine.run(tasks=task_list, error_fn=err)
            if save_events is not None:
                result.events.to_jsonl(save_events)

            backend_name = self.backend if isinstance(self.backend, str) else self.backend.name
            job = SolveJob(
                job_id=job_id,
                spec=spec,
                q=int(q),
                backend=backend_name,
                result=result,
                summary=result.summary(deadline=self.config.deadline_s),
            )
            self.jobs.append(job)
            return job

    # ------------------------------------------------------------------ telemetry

    def telemetry(self) -> Dict:
        """Aggregate report over every admitted job (the serving dashboard dict)."""
        n = len(self.jobs)
        agg: Dict = {
            "jobs": n,
            "backend": self.backend if isinstance(self.backend, str) else self.backend.name,
        }
        if n == 0:
            return agg
        for k in ("retries", "timeouts", "drops", "cancelled", "dispatched"):
            agg[k] = int(sum(j.summary.get(k, 0) for j in self.jobs))
        agg["effective_q_mean"] = float(np.mean([j.summary["effective_q"] for j in self.jobs]))
        agg["sim_makespan_s_mean"] = float(np.mean([j.summary["sim_makespan_s"] for j in self.jobs]))
        agg["stopped_early"] = int(sum(bool(j.summary.get("stopped_early")) for j in self.jobs))
        agg["per_job"] = [
            {
                "job_id": j.job_id,
                "q": j.q,
                "effective_q": j.summary["effective_q"],
                "retries": j.summary["retries"],
                "timeouts": j.summary["timeouts"],
                "drops": j.summary["drops"],
                "sim_makespan_s": j.summary["sim_makespan_s"],
                "final_error": j.summary.get("final_error"),
            }
            for j in self.jobs
        ]
        return agg

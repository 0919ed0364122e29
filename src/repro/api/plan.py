"""The backend planner: resolve ``"auto"`` specs into an explicit plan.

:func:`plan_execution` inspects the problem shape (n vs p, batch size),
the path spec (CV fold geometry), the device kind and the shared
working-set :class:`~repro.serve.buckets.BucketRegistry`, and resolves a
(:class:`~repro.api.specs.Problem`, :class:`~repro.api.specs.PathSpec`,
:class:`~repro.api.specs.SolverPolicy`) triple into an immutable
:class:`ExecutionPlan`: which backend runs (host gathered / device masked /
device compact / served), at what working-set bucket, with what padding,
and — crucially — *why*, as a human-readable :meth:`ExecutionPlan.explain`
report.  The decision rules encode the repo's measured trade-offs
(ROADMAP "when each backend wins"):

* a single unbatched problem → the gathered **host** driver (column
  gathers shrink every matvec; the device scan pays off at B ≥ 2);
* a batch (or CV folds) with n ≳ p → the **masked** device engine
  (screening keeps ≥ p/2, compaction has nothing to cut);
* a batch with p ≫ n (and a W bucket < p) → the **compact** device engine
  (inner solves cost O(n·W), not O(n·p));
* serving → the same masked/compact rule at the canonical bucket shape,
  so plan decisions are identical between direct and served execution of
  the same spec triple.

The planner only *previews* — execution passes the policy's raw knobs to
the engines, which re-resolve through the same registry/rules, so a plan
can never desynchronize from what actually runs.
"""

from __future__ import annotations

import dataclasses

import jax

from ..core.engine import _WS_BUCKETS, _ws_bucket, second_tier_width
from ..serve.buckets import default_policy
from .specs import PathSpec, Problem, SolverPolicy

__all__ = ["ExecutionPlan", "plan_execution"]


@dataclasses.dataclass(frozen=True)
class ExecutionPlan:
    """One resolved execution choice, with its reasons.

    ``backend`` is ``"host"`` / ``"device"`` / ``"serve"``; ``mode`` the
    concrete engine (``"gathered"`` / ``"masked"`` / ``"compact"``);
    ``working_set`` the previewed compact bucket W (None outside compact
    mode); ``ws_tiers`` the previewed tier widths — ``(W,)`` single-tier or
    ``(W, 2W)`` two-tier (None outside compact mode); ``exec_shape`` the
    padded ``(slots, N, P)`` program shape when ``pad="bucket"`` (slots is
    ``ShapeBucketPolicy.direct_slots`` for direct plans, None for served
    plans — the slot count is the serving deployment's batch bucket).
    """

    backend: str
    mode: str
    batch: int
    n: int
    p: int
    working_set: int | None
    ws_tiers: tuple | None
    pad: str | None
    exec_shape: tuple | None
    screening: str
    device: str
    reasons: tuple[str, ...]

    def summary(self) -> str:
        """Compact one-token summary (CSV/JSON friendly)."""
        s = f"{self.backend}/{self.mode}"
        if self.working_set is not None:
            s += f"-W{self.working_set}"
            if self.ws_tiers is not None and len(self.ws_tiers) == 2:
                s += f"+{self.ws_tiers[1]}"
        if self.exec_shape is not None:
            s += "@" + "x".join("?" if v is None else str(v)
                                for v in self.exec_shape)
        elif self.batch > 1:
            s += f"-B{self.batch}"
        return s

    def explain(self) -> str:
        """Multi-line report of the plan and why each choice was made."""
        head = (f"ExecutionPlan: {self.backend}/{self.mode}"
                f"  B={self.batch}  n={self.n}  p={self.p}"
                + (f"  W={self.working_set}" if self.working_set is not None
                   else "")
                + (f"  tiers={self.ws_tiers}" if self.ws_tiers is not None
                   else "")
                + f"  pad={self.pad}"
                + (f"  exec_shape={self.exec_shape}"
                   if self.exec_shape is not None else "")
                + f"  device={self.device}")
        return "\n".join([head] + [f"  - {r}" for r in self.reasons])


def _preview_ws(working_set, n_key: int, p_key: int, key: tuple,
                reasons: list) -> int:
    """Resolve the compact bucket W exactly as the engine will, and record
    where it came from (explicit / registry growth / default recipe)."""
    grown = key in _WS_BUCKETS
    if isinstance(working_set, int) and not isinstance(working_set, bool):
        W = _ws_bucket(working_set, n_key, p_key, key)
        reasons.append(f"W={W}: explicit working_set={working_set} rounded "
                       f"to a power-of-two bucket capped at p")
        return W
    W = _ws_bucket("auto", n_key, p_key, key)
    if grown:
        reasons.append(f"W={W}: grow-on-overflow registry entry for "
                       f"{key} (a previous same-shape run overflowed)")
    else:
        reasons.append(f"W={W}: auto recipe min(2^⌈log₂ max(2n, 64)⌉, p) — "
                       f"the screened set tracks the active set, which p ≫ n "
                       f"keeps well under n")
    return W


def plan_execution(problem: Problem, path: PathSpec | None = None,
                   policy: SolverPolicy | None = None) -> ExecutionPlan:
    """Resolve the spec triple into an explicit, introspectable plan."""
    path = path if path is not None else PathSpec()
    policy = policy if policy is not None else SolverPolicy()
    family = problem.family
    m = family.n_classes
    n, p = problem.n, problem.p
    batched = problem.batched
    B = problem.batch
    device = jax.default_backend()
    reasons: list[str] = []

    n_fit = n
    if path.cv_folds:
        if batched:
            raise ValueError("CV takes a single (n, p) problem, not a batch")
        if policy.backend == "host":
            raise ValueError(
                "cross-validation runs all folds as ONE batched device "
                "program; backend='host' cannot execute cv_folds — use "
                "'auto', 'masked', 'compact' or 'serve'")
        B, batched = path.cv_folds, True
        n_fit = n - n // path.cv_folds
        reasons.append(
            f"{path.cv_folds}-fold CV: {B} equal-shape training designs of "
            f"{n_fit}×{p} batch into one compiled program")

    rs = path.resample
    if rs is not None:
        if batched:
            raise ValueError(
                "resampling takes a single (n, p) problem — the replicate "
                "axis IS the batch axis (B members share one design)")
        if policy.backend == "host":
            raise ValueError(
                "resampling runs all replicates as ONE weight-fused device "
                "program against the shared design; backend='host' cannot "
                "execute a ResamplePlan — use 'auto', 'masked', 'compact' "
                "or 'serve'")
        B, batched = rs.n_replicates, True
        reasons.append(
            f"{rs.kind} resampling: B={B} replicates share ONE {n}×{p} "
            f"design via per-member row weights (O(n·p + B·n) memory, "
            f"no (B, n, p) materialization)")

    serve = policy.backend == "serve"

    # -- SLO knobs route through the serving layer --------------------------
    slo = policy.deadline_ms is not None or policy.priority != 0
    if slo and policy.backend not in ("auto", "serve"):
        raise ValueError(
            f"deadline_ms/priority are serving SLO knobs — only a service "
            f"(timer-driven flush, priority queues) can enforce them; they "
            f"cannot be honoured with backend={policy.backend!r}")
    if slo and policy.backend == "auto":
        serve = True
        reasons.append(
            "backend='serve': deadline_ms/priority set — SLOs are enforced "
            "by the serving layer (timer-driven deadline flush, priority "
            "admission queues)")

    # -- padding & canonical execution shape --------------------------------
    pad = policy.pad
    if pad == "auto":
        pad = "bucket" if serve else None
        reasons.append(
            "pad='bucket': served requests run at canonical bucket shapes "
            "so heterogeneous traffic shares compiled programs" if serve else
            "pad=None: direct execution keeps native shapes (canonical "
            "buckets pay off for heterogeneous served streams)")
    if serve and pad != "bucket":
        raise ValueError(
            "the serving layer always executes at canonical bucket shapes; "
            "SolverPolicy(pad=None) cannot be honoured with "
            "backend='serve' — use pad='auto' or 'bucket'")
    if rs is not None and not serve and pad == "bucket":
        raise ValueError(
            "direct replicate execution runs at the shared design's native "
            "shape (the weights are O(B·n) — there is nothing to bucket); "
            "pad='bucket' with a ResamplePlan requires backend='serve'")
    exec_shape = None
    n_key, p_key = n_fit, p
    if pad == "bucket":
        pol = default_policy()
        N, P = pol.shape_bucket(n_fit, p, family.name)
        slots = None if serve else pol.direct_slots(B)
        exec_shape = (slots, N, P)
        n_key, p_key = N, P
        reasons.append(
            f"canonical execution shape rows×cols = {N}×{P} "
            f"(power-of-two buckets, inert zero padding; rows padded for "
            f"OLS only)")
        if not serve:
            reasons.append(
                f"{slots} batch slots for {B} problem(s): the default "
                f"services' width, so the fit runs the programs a served "
                f"request runs (bitwise to it); dummy slots cost device "
                f"work")

    # -- backend ------------------------------------------------------------
    if policy.backend == "host":
        if batched:
            raise ValueError(
                "backend='host' takes a single (n, p) problem; the gathered "
                "host driver cannot run a (B, n, p) batch — use 'masked', "
                "'compact' or 'auto'")
        backend, mode = "host", "gathered"
        reasons.append("policy pinned the gathered host driver")
    elif policy.backend in ("masked", "compact"):
        backend, mode = "device", policy.backend
        reasons.append(f"policy pinned the {policy.backend} device engine")
    elif not serve and not batched:
        backend, mode = "host", "gathered"
        reasons.append(
            "single unbatched problem: gathered host sub-problems beat "
            "masked full-width device solves (the device scan pays off for "
            "batches, CV folds and served streams)")
    else:
        backend = "serve" if serve else "device"
        mode = None  # resolved below

    # -- masked vs compact --------------------------------------------------
    if mode is None:
        ws = policy.working_set
        if ws is None:
            mode = "masked"
            reasons.append("working_set=None forbids compaction: masked "
                           "full-width engine")
        elif isinstance(ws, int) and not isinstance(ws, bool):
            mode = "compact"
            reasons.append(f"working_set={ws} pins the compact engine")
        elif policy.screening == "none":
            mode = "masked"
            reasons.append("screening='none' keeps all p predictors in "
                           "every working set — nothing to compact")
        elif p >= 2 * n_fit:
            key = (n_key, p_key, m, family.name, policy.screening)
            probe: list[str] = []
            W = _preview_ws("auto", n_key, p_key, key, probe)
            if W < p_key:
                mode = "compact"
                reasons.append(
                    f"p={p} ≫ n={n_fit} (p ≥ 2n): compact working-set "
                    f"engine — inner solves cost O(n·W) instead of O(n·p)")
                reasons.extend(probe)
            else:
                mode = "masked"
                reasons.append(
                    f"p={p} ≥ 2n but the auto W bucket ({W}) already spans "
                    f"p: compaction would cut nothing — masked engine")
        else:
            mode = "masked"
            reasons.append(
                f"n={n_fit} ≳ p={p} (p < 2n): screening keeps ≥ p/2 of the "
                f"predictors, compaction cuts nothing — masked full-width "
                f"engine")

    # -- working-set preview for pinned-compact plans ------------------------
    working_set = None
    ws_tiers = None
    if mode == "compact":
        key = (n_key, p_key, m, family.name, policy.screening)
        ws_probe: list[str] = []
        working_set = _preview_ws(policy.working_set, n_key, p_key, key,
                                  ws_probe)
        # avoid duplicating the auto-recipe reason added by the heuristic
        if not any(r.startswith("W=") for r in reasons):
            reasons.extend(ws_probe)
        # the second tier derives from the already-previewed W (the same
        # recipe the engine applies after its own registry read) — a single
        # registry lookup, so the previewed pair is internally consistent
        # even if a concurrent run grows the shared registry mid-plan
        W2 = second_tier_width(working_set, policy.ws_tiers, p_key)
        ws_tiers = (working_set,) if W2 is None else (working_set, W2)
        if W2 is None:
            reasons.append(
                "single-tier compact: ws_tiers=1 pinned it" if
                policy.ws_tiers == 1 else
                f"single-tier compact: a 2W tier ({2 * working_set}) would "
                f"span p={p_key} — the masked fallback IS the top tier")
        else:
            reasons.append(
                f"two-tier compact W={working_set}+{W2}: a member whose "
                f"screened set outgrows W is served at 2W; the batch-wide "
                f"masked fallback fires only beyond {W2}")

    if backend == "host" and pad == "bucket":
        raise ValueError("pad='bucket' requires a device or serve backend "
                         "(the host driver gathers sub-problems; it has no "
                         "use for canonical padded shapes)")

    reasons.append(f"jax default backend: {device}")
    return ExecutionPlan(
        backend=backend, mode=mode, batch=B, n=n_fit, p=p,
        working_set=working_set, ws_tiers=ws_tiers, pad=pad,
        exec_shape=exec_shape, screening=policy.screening, device=device,
        reasons=tuple(reasons),
    )

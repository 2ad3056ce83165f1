"""In-flight HEFT rescheduling driven by streaming prediction drift.

The planner plugs into `workflow.simulator.execute_adaptive`: every
completion is fed to the OnlinePredictor; predictions for the not-yet-
started frontier are then re-evaluated in one batched service call.  When
any task's new mean falls outside the uncertainty band snapshotted at the
last planning pass (|new - ref| > z * ref_std), the frontier is re-planned
with HEFT under the updated posteriors — running tasks keep their nodes,
data already produced constrains ready times (finish + comm from the
producing node to each candidate).

Every planning pass goes through the decision plane, and the plane is
resident: a `FusedPlane` keeps the raw predictive rows for the whole
workflow across passes and re-gathers only the rows whose store blocks
moved (generation-tagged dirty tracking), so a planning pass costs a
dirty-subset predict — not a full gather — plus the one HEFT engine
(`sched.fused.fused_heft_schedule`, bitwise equal to
`heft.heft_schedule_reference`; small frontiers take the NumPy sweep,
large ones one jitted dispatch, chosen by size).  The drift bands and the
speculation policy read the same resident matrix.

With `repro.obs` on, a completion is the span `lotaru.plan.completion`,
holding `lotaru.plan.observe` (the posterior update), `lotaru.plan.drift`
(the frontier's batched predict and the band check) and, on drift,
`lotaru.plan.replan`; a run's first plan is `lotaru.plan.initial`.
Counters: `lotaru.plan.completions`, `lotaru.plan.replans` and
`lotaru.plan.frontier_cells` (replanned tasks x nodes).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Mapping, Optional, Tuple

import numpy as np

from repro import obs
from repro.core.extrapolation import MachineBench
from repro.core.microbench import NodeSpec
from repro.online.events import PredictionQuery, TaskCompletion
from repro.online.predictor import OnlinePredictor
from repro.online.service import PredictionService
from repro.sched.fused import FusedPlane, fused_heft_schedule
from repro.sched.heft import Schedule, comm_seconds
from repro.sched.plane import PredictionMatrix, TaskDistribution
from repro.sched.straggler import SpeculationDecision, decide_speculation
from repro.workflow.dag import TaskInstance, WorkflowDAG
from repro.workflow.simulator import ExecRecord, SimState


@dataclass
class RescheduleStats:
    completions: int = 0
    drift_events: int = 0
    reschedules: int = 0


class OnlineReschedulingPlanner:
    def __init__(self, dag: WorkflowDAG, nodes: List[NodeSpec],
                 online: OnlinePredictor,
                 benches: Optional[Mapping[str, MachineBench]] = None,
                 z: float = 1.96, cooldown: int = 0,
                 store=None, tenant: str = "default",
                 workflow: Optional[str] = None,
                 quantile: Optional[float] = None):
        """z: band half-width in predictive stds; cooldown: minimum
        completions between two re-planning passes (0 = none); store: a
        shared PosteriorStore so several concurrent workflows/tenants serve
        from one stack (each planner binds the namespace tenant/workflow,
        defaulting workflow to dag.name — pass a run-unique workflow id
        when executing the same workflow type concurrently, or a later
        planner displaces the earlier one's binding); quantile: schedule on
        the pessimistic mean + z*std at this quantile instead of the mean
        (uncertainty-aware HEFT)."""
        self.dag = dag
        self.nodes = nodes
        self.online = online
        if benches:
            self.online.benches.update(benches)
        # the merged registry, so a planner built from an already-configured
        # OnlinePredictor needs no benches arg (and a partial arg extends,
        # never shadows, what the predictor knows); z forwarded so the drift
        # band actually widens/narrows with the knob
        self.service = PredictionService(online, online.benches, z=z,
                                         store=store, tenant=tenant,
                                         workflow=workflow or dag.name)
        self.z = z
        self.cooldown = cooldown
        self.quantile = quantile
        # device-resident decision plane over the WHOLE workflow: planning
        # passes re-gather only dirty rows; frontier matrices are row
        # subsets of the resident one (elementwise per row -> bitwise
        # equal to a fresh per-frontier gather)
        self._plane = FusedPlane(self.service, nodes, dag=dag)
        # every frontier sub-DAG packs its deps to the whole DAG's fan-in,
        # so a run's jitted sweeps differ in the task bucket alone
        self._dep_width = max((len(t.deps) for t in dag.tasks.values()),
                              default=0)
        self.stats = RescheduleStats()
        self._since_resched = 10 ** 9
        # uid -> (ref mean, ref std) on its currently-assigned node
        self._band: Dict[str, Tuple[float, float]] = {}
        self._assignment: Dict[str, str] = {}
        # last-planned matrix rows per uid (means/stds over all nodes) —
        # what the speculation policy reads for running tasks
        self._dist_rows: Dict[str, TaskDistribution] = {}

    @property
    def plane(self) -> FusedPlane:
        """The resident decision plane every planning pass is served
        from."""
        return self._plane

    # ---- batched prediction matrix ------------------------------------------
    def _prediction_matrix(self, uids) -> PredictionMatrix:
        """The decision-plane matrix for `uids` x nodes, served from the
        resident `FusedPlane` — a planning pass costs a dirty-row gather +
        predict (usually a handful of rows), not a full T x N rebuild
        (rank + placement + bands + speculation all read from this)."""
        uids = list(uids)
        full = self._plane.matrix()
        if len(uids) == len(full.uids):
            mat = full
        else:
            rows = np.asarray([full.uid_index[u] for u in uids], np.int64)
            mat = PredictionMatrix(tuple(uids), tuple(full.node_names),
                                   full.means[rows], full.stds[rows])
        for u in uids:
            self._dist_rows[u] = mat.row(u)
        return mat

    def _snapshot_bands(self, mat: PredictionMatrix,
                        assignment: Dict[str, str],
                        uids: Optional[set] = None) -> None:
        for uid, name in assignment.items():
            if uids is not None and uid not in uids:
                continue
            self._band[uid] = mat.on(uid, name)
        self._assignment.update(assignment)

    # ---- executor protocol --------------------------------------------------
    def initial_schedule(self) -> Schedule:
        with obs.span("lotaru.plan.initial"):
            mat = self._prediction_matrix(self.dag.tasks)
            sched = fused_heft_schedule(self.dag, self.nodes, mat,
                                        quantile=self.quantile,
                                        rank_cache=self._plane.rank_cache,
                                        dep_width=self._dep_width)
        self._band.clear()
        self._snapshot_bands(mat, sched.assignment)
        self._since_resched = 10 ** 9
        return sched

    def on_completion(self, rec: ExecRecord, state: SimState
                      ) -> Optional[Schedule]:
        with obs.span("lotaru.plan.completion"):
            obs.count("lotaru.plan.completions", 1)
            t = self.dag.tasks[rec.uid]
            self.stats.completions += 1
            self._since_resched += 1
            if rec.attempt == 0:
                # failure re-runs (attempt > 0) span recovery downtime —
                # their wall time is not the task's runtime, so they never
                # reach the posterior
                with obs.span("lotaru.plan.observe"):
                    self.online.observe(TaskCompletion(
                        workflow=t.workflow, uid=rec.uid, task=t.task_name,
                        node=rec.node, input_gb=t.input_gb,
                        runtime_s=rec.finish - rec.start,
                        finish_time=rec.finish))

            frontier = [u for u in self.dag.tasks if u not in state.started]
            if not frontier:
                return None
            with obs.span("lotaru.plan.drift"):
                drifted = self._drifted(frontier)
            if not drifted:
                return None
            self.stats.drift_events += 1
            if self._since_resched <= self.cooldown:
                return None
            self._since_resched = 0
            self.stats.reschedules += 1
            obs.count("lotaru.plan.replans", 1)
            obs.count("lotaru.plan.frontier_cells",
                      len(frontier) * len(self.nodes))
            with obs.span("lotaru.plan.replan"):
                return self._replan(state, set(frontier))

    def _drifted(self, frontier: List[str]) -> bool:
        """One batched sweep over the frontier on its assigned nodes: has
        any task's mean left the band snapshotted at the last plan?"""
        queries = [PredictionQuery(self.dag.tasks[u].task_name,
                                   self._assignment[u],
                                   self.dag.tasks[u].input_gb)
                   for u in frontier]
        preds = self.service.predict_batch(queries)
        for u, (mean, _, _) in zip(frontier, preds):
            ref_mean, ref_std = self._band[u]
            if abs(mean - ref_mean) > self.z * max(ref_std, 1e-9):
                return True
        return False

    # ---- speculation policy -------------------------------------------------
    def decide_speculation(self, uid: str, node: str, elapsed_s: float,
                           idle_nodes: List[NodeSpec],
                           q: float = 0.95) -> SpeculationDecision:
        """Uncertainty-driven straggler verdict for a running task, read
        from its last-planned decision-plane row (simulator protocol for
        `execute_adaptive(speculation=...)`)."""
        row = self._dist_rows.get(uid)
        if row is None or node not in row.node_names:
            return SpeculationDecision(threshold_s=float("inf"),
                                       speculate=False)
        return decide_speculation(elapsed_s, row, node, idle_nodes, q=q)

    # ---- frontier re-planning -----------------------------------------------
    def _replan(self, state: SimState, frontier: set) -> Schedule:
        """HEFT over the unstarted sub-DAG; booked/finished work enters as
        ready-time constraints (finish + comm from the producing node).

        Running tasks' finishes are NOT known to a real resource manager —
        they are estimated as start + predicted duration (never before
        now), so the adaptive benchmark measures the online predictor, not
        simulator oracle knowledge."""
        sub = WorkflowDAG(self.dag.name)
        for u in self.dag.topo_order():
            if u not in frontier:
                continue
            t = self.dag.tasks[u]
            sub.add(TaskInstance(
                uid=u, task_name=t.task_name, workflow=t.workflow,
                input_gb=t.input_gb, output_gb=t.output_gb, sample=t.sample,
                deps=[d for d in t.deps if d in frontier]))

        mat = self._prediction_matrix(sub.tasks)
        node_by_name = {n.name: n for n in self.nodes}
        # running tasks only need a prediction on their assigned node
        running = list(state.running.items())
        run_preds = self.service.predict_batch(
            [PredictionQuery(self.dag.tasks[u].task_name, name,
                             self.dag.tasks[u].input_gb)
             for u, (name, _) in running])
        done_at: Dict[str, Tuple[str, float]] = dict(state.finished)
        node_avail = {n.name: state.now for n in self.nodes}
        for (u, (name, start)), (mean, _, _) in zip(running, run_preds):
            est_end = max(state.now, start + float(mean))
            done_at[u] = (name, est_end)
            node_avail[name] = max(node_avail[name], est_end)

        def ready_at(uid: str, node: NodeSpec) -> float:
            ready = state.now
            for d in self.dag.tasks[uid].deps:
                if d in frontier:
                    continue
                dn_name, end = done_at[d]
                ready = max(ready, end + comm_seconds(
                    self.dag.tasks[d].output_gb, node_by_name[dn_name], node))
            return ready

        new_sched = fused_heft_schedule(sub, self.nodes, mat,
                                        quantile=self.quantile,
                                        ready_at=ready_at,
                                        node_available=node_avail,
                                        rank_cache=self._plane.rank_cache,
                                        dep_width=self._dep_width)
        self._snapshot_bands(mat, new_sched.assignment, frontier)
        return new_sched

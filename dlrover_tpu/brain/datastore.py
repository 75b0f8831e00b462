"""Brain datastore: persistent cross-job metric history.

Reference: ``dlrover/go/brain/pkg/datastore/`` — a MySQL-backed store of
job metadata + runtime metrics that the optimizer algorithms mine.  The
TPU build uses sqlite (single file, zero-dependency, transactional),
which matches the deployment shape: one Brain per cluster, modest write
rates (one sample per job per ~30 s), read-mostly optimization queries.
"""

import json
import os
import sqlite3
import threading
import time
from dataclasses import asdict, dataclass, field
from typing import Dict, List, Optional


@dataclass
class JobRecord:
    """One job's identity + outcome (reference datastore job table)."""

    job_uuid: str
    job_name: str = ""
    # Signature fields drive similarity matching across jobs: same model
    # scale + workload type ⇒ history is transferable.
    model_signature: str = ""  # e.g. "gpt2-small-124M"
    workload: str = "jax"  # jax | torch | custom
    worker_num: int = 0
    node_unit: int = 1
    status: str = "running"  # running | completed | failed | oom
    created_at: float = field(default_factory=time.time)
    finished_at: float = 0.0
    extra: Dict = field(default_factory=dict)


@dataclass
class JobProfile:
    """Workload shape features for cross-model similarity.

    The reference Brain sizes new jobs from *exact* job-name history
    (``optimize_job_worker_create_resource.go`` keys on job cohorts); at
    fleet scale a brand-new model has no exact cohort, but its SHAPE
    (parameter count, step FLOPs, batch tokens) predicts which history
    transfers. Distances are computed in log-space — a 124M and a 350M
    model are "one doubling and a bit" apart regardless of absolute
    scale.
    """

    job_uuid: str
    param_count: float = 0.0  # model parameters
    flops_per_step: float = 0.0  # fwd+bwd FLOPs per optimizer step
    tokens_per_batch: float = 0.0  # global batch tokens per step
    seq_len: int = 0
    arch: str = ""  # model family: gpt | llama | moe | ...


def transformer_profile(
    job_uuid: str,
    n_params: float,
    global_batch: int,
    seq_len: int,
    arch: str = "gpt",
) -> JobProfile:
    """Profile for a dense-transformer LM job from first principles:
    tokens = batch*seq, step FLOPs ≈ 6*N*tokens (fwd 2N + bwd 4N per
    token) — the same accounting benchmark/flops.py's MFU uses."""
    tokens = float(global_batch) * float(seq_len)
    return JobProfile(
        job_uuid=job_uuid,
        param_count=float(n_params),
        flops_per_step=6.0 * float(n_params) * tokens,
        tokens_per_batch=tokens,
        seq_len=int(seq_len),
        arch=arch,
    )


def profile_distance(a: JobProfile, b: JobProfile) -> float:
    """Log-space L1 distance over the shape features present on BOTH
    profiles, plus a flat penalty for an architecture-family mismatch
    (a MoE's step economics don't transfer to a dense model 1:1).

    The per-feature distances are combined as a WEIGHTED MEAN, not a
    sum: params and step FLOPs are near-perfectly correlated at equal
    batch tokens (flops ≈ 6·N·tokens), so a sum would double-count
    model scale and halve the effective transfer range.

    At least one SCALE feature (param count or step FLOPs) must be
    comparable: tokens-per-batch alone says nothing about model scale,
    and a distance built only on it would rank a 124M donor as an
    exact match for a 70B probe."""
    import math

    d = 0.0
    total_weight = 0.0
    scale_features = 0
    for attr, weight in (
        ("param_count", 1.0),
        ("flops_per_step", 1.0),
        ("tokens_per_batch", 0.5),
    ):
        va, vb = getattr(a, attr), getattr(b, attr)
        if va > 0 and vb > 0:
            d += weight * abs(math.log(va / vb))
            total_weight += weight
            if attr != "tokens_per_batch":
                scale_features += 1
    if scale_features == 0:
        return float("inf")
    d /= total_weight
    if a.arch and b.arch and a.arch != b.arch:
        d += 1.0
    return d


@dataclass
class JobMetricSample:
    """One runtime observation of a running job."""

    job_uuid: str
    timestamp: float = field(default_factory=time.time)
    world_size: int = 0
    steps_per_second: float = 0.0
    tokens_per_second: float = 0.0
    peak_memory_mb: float = 0.0
    cpu_percent: float = 0.0


class BrainDataStore:
    """Thread-safe sqlite store. ``path=':memory:'`` for tests."""

    def __init__(self, path: str = ":memory:"):
        if path != ":memory:":
            os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        self._conn = sqlite3.connect(path, check_same_thread=False)
        self._mu = threading.Lock()
        with self._mu:
            self._conn.executescript(
                """
                CREATE TABLE IF NOT EXISTS jobs (
                    job_uuid TEXT PRIMARY KEY,
                    job_name TEXT,
                    model_signature TEXT,
                    workload TEXT,
                    worker_num INTEGER,
                    node_unit INTEGER,
                    status TEXT,
                    created_at REAL,
                    finished_at REAL,
                    extra TEXT
                );
                CREATE TABLE IF NOT EXISTS metrics (
                    job_uuid TEXT,
                    timestamp REAL,
                    world_size INTEGER,
                    steps_per_second REAL,
                    tokens_per_second REAL,
                    peak_memory_mb REAL,
                    cpu_percent REAL
                );
                CREATE INDEX IF NOT EXISTS idx_metrics_job
                    ON metrics (job_uuid, timestamp);
                CREATE TABLE IF NOT EXISTS events (
                    job_uuid TEXT,
                    timestamp REAL,
                    event_type TEXT,
                    node_id INTEGER,
                    detail TEXT
                );
                CREATE TABLE IF NOT EXISTS profiles (
                    job_uuid TEXT PRIMARY KEY,
                    param_count REAL,
                    flops_per_step REAL,
                    tokens_per_batch REAL,
                    seq_len INTEGER,
                    arch TEXT
                );
                """
            )
            self._conn.commit()

    # -- jobs --------------------------------------------------------------

    def upsert_job(self, job: JobRecord) -> None:
        with self._mu:
            self._conn.execute(
                "INSERT INTO jobs VALUES (?,?,?,?,?,?,?,?,?,?) "
                "ON CONFLICT(job_uuid) DO UPDATE SET "
                "job_name=excluded.job_name, "
                "model_signature=excluded.model_signature, "
                "workload=excluded.workload, "
                "worker_num=excluded.worker_num, "
                "node_unit=excluded.node_unit, "
                "status=excluded.status, "
                "finished_at=excluded.finished_at, "
                "extra=excluded.extra",
                (
                    job.job_uuid,
                    job.job_name,
                    job.model_signature,
                    job.workload,
                    job.worker_num,
                    job.node_unit,
                    job.status,
                    job.created_at,
                    job.finished_at,
                    json.dumps(job.extra),
                ),
            )
            self._conn.commit()

    def update_job_status(self, job_uuid: str, status: str) -> None:
        finished = (
            time.time() if status in ("completed", "failed", "oom") else 0.0
        )
        with self._mu:
            self._conn.execute(
                "UPDATE jobs SET status=?, finished_at=? WHERE job_uuid=?",
                (status, finished, job_uuid),
            )
            self._conn.commit()

    def get_job(self, job_uuid: str) -> Optional[JobRecord]:
        with self._mu:
            row = self._conn.execute(
                "SELECT * FROM jobs WHERE job_uuid=?", (job_uuid,)
            ).fetchone()
        return self._row_to_job(row) if row else None

    def similar_jobs(
        self,
        model_signature: str,
        workload: str = "",
        status: str = "completed",
        limit: int = 50,
    ) -> List[JobRecord]:
        """History transferable to a new job: same model signature (and
        workload, when given), most recent first."""
        q = "SELECT * FROM jobs WHERE model_signature=? AND status=?"
        args: List = [model_signature, status]
        if workload:
            q += " AND workload=?"
            args.append(workload)
        q += " ORDER BY created_at DESC LIMIT ?"
        args.append(limit)
        with self._mu:
            rows = self._conn.execute(q, args).fetchall()
        return [self._row_to_job(r) for r in rows]

    # -- profiles ----------------------------------------------------------

    def upsert_profile(self, profile: JobProfile) -> None:
        with self._mu:
            self._conn.execute(
                "INSERT INTO profiles VALUES (?,?,?,?,?,?) "
                "ON CONFLICT(job_uuid) DO UPDATE SET "
                "param_count=excluded.param_count, "
                "flops_per_step=excluded.flops_per_step, "
                "tokens_per_batch=excluded.tokens_per_batch, "
                "seq_len=excluded.seq_len, "
                "arch=excluded.arch",
                (
                    profile.job_uuid,
                    profile.param_count,
                    profile.flops_per_step,
                    profile.tokens_per_batch,
                    profile.seq_len,
                    profile.arch,
                ),
            )
            self._conn.commit()

    def get_profile(self, job_uuid: str) -> Optional[JobProfile]:
        with self._mu:
            row = self._conn.execute(
                "SELECT * FROM profiles WHERE job_uuid=?", (job_uuid,)
            ).fetchone()
        return self._row_to_profile(row) if row else None

    def nearest_profiles(
        self,
        profile: JobProfile,
        k: int = 8,
        status: str = "completed",
        limit: int = 500,
    ) -> List[tuple]:
        """The ``k`` profiled jobs (of the given status, most recent
        ``limit`` considered) nearest to ``profile`` in workload-shape
        space: ``[(JobRecord, JobProfile, distance), ...]`` ascending.
        This is the fleet-scale warm-start query — a new model with no
        exact-signature cohort borrows history from shape-similar jobs.
        """
        with self._mu:
            rows = self._conn.execute(
                "SELECT j.job_uuid, p.param_count, p.flops_per_step, "
                "p.tokens_per_batch, p.seq_len, p.arch "
                "FROM jobs j JOIN profiles p ON j.job_uuid = p.job_uuid "
                "WHERE j.status=? AND j.job_uuid != ? "
                "ORDER BY j.created_at DESC LIMIT ?",
                (status, profile.job_uuid, limit),
            ).fetchall()
        scored = []
        for r in rows:
            cand = self._row_to_profile(r)
            d = profile_distance(profile, cand)
            if d != float("inf"):
                scored.append((cand, d))
        scored.sort(key=lambda t: t[1])
        out = []
        for cand, d in scored[:k]:
            job = self.get_job(cand.job_uuid)
            if job is not None:
                out.append((job, cand, d))
        return out

    # -- fleet aggregates --------------------------------------------------

    def fleet_summary(self) -> Dict:
        """Per-signature fleet aggregates (reference Brain's cluster
        stats processors): job counts by outcome, the best observed
        speed and the peak memory across each cohort — the ops-facing
        view of what the datastore knows."""
        with self._mu:
            rows = self._conn.execute(
                "SELECT model_signature, status, COUNT(*) "
                "FROM jobs GROUP BY model_signature, status"
            ).fetchall()
            worker_rows = self._conn.execute(
                "SELECT model_signature, AVG(worker_num) "
                "FROM jobs GROUP BY model_signature"
            ).fetchall()
            speed_rows = self._conn.execute(
                "SELECT j.model_signature, MAX(m.steps_per_second), "
                "MAX(m.peak_memory_mb) FROM jobs j "
                "JOIN metrics m ON j.job_uuid = m.job_uuid "
                "GROUP BY j.model_signature"
            ).fetchall()
        cohorts: Dict[str, Dict] = {}
        for sig, status, count in rows:
            c = cohorts.setdefault(
                sig or "?", {"jobs": 0, "by_status": {}, "avg_workers": 0.0}
            )
            c["jobs"] += count
            c["by_status"][status] = count
        for sig, avg_workers in worker_rows:
            cohorts.setdefault(sig or "?", {"jobs": 0, "by_status": {}})[
                "avg_workers"
            ] = round(float(avg_workers or 0.0), 1)
        for sig, best_speed, peak_mem in speed_rows:
            c = cohorts.setdefault(sig or "?", {"jobs": 0, "by_status": {}})
            c["best_steps_per_s"] = round(float(best_speed or 0.0), 3)
            c["peak_memory_mb"] = round(float(peak_mem or 0.0), 1)
        total = sum(c["jobs"] for c in cohorts.values())
        return {"cohorts": cohorts, "total_jobs": total}

    # -- metrics -----------------------------------------------------------

    def add_metric(self, sample: JobMetricSample) -> None:
        with self._mu:
            self._conn.execute(
                "INSERT INTO metrics VALUES (?,?,?,?,?,?,?)",
                (
                    sample.job_uuid,
                    sample.timestamp,
                    sample.world_size,
                    sample.steps_per_second,
                    sample.tokens_per_second,
                    sample.peak_memory_mb,
                    sample.cpu_percent,
                ),
            )
            self._conn.commit()

    def job_metrics(
        self, job_uuid: str, since: float = 0.0, limit: int = 1000
    ) -> List[JobMetricSample]:
        with self._mu:
            rows = self._conn.execute(
                "SELECT * FROM metrics WHERE job_uuid=? AND timestamp>=? "
                "ORDER BY timestamp ASC LIMIT ?",
                (job_uuid, since, limit),
            ).fetchall()
        return [
            JobMetricSample(
                job_uuid=r[0],
                timestamp=r[1],
                world_size=r[2],
                steps_per_second=r[3],
                tokens_per_second=r[4],
                peak_memory_mb=r[5],
                cpu_percent=r[6],
            )
            for r in rows
        ]

    def speed_by_world_size(self, job_uuids: List[str]) -> Dict[int, float]:
        """Best observed steps/s per world size across the given jobs —
        the scaling curve the create-stage optimizer mines."""
        if not job_uuids:
            return {}
        marks = ",".join("?" * len(job_uuids))
        with self._mu:
            rows = self._conn.execute(
                f"SELECT world_size, MAX(steps_per_second) FROM metrics "
                f"WHERE job_uuid IN ({marks}) AND world_size>0 "
                f"GROUP BY world_size",
                job_uuids,
            ).fetchall()
        return {int(w): float(s) for w, s in rows if s}

    def peak_memory(self, job_uuids: List[str]) -> float:
        if not job_uuids:
            return 0.0
        marks = ",".join("?" * len(job_uuids))
        with self._mu:
            row = self._conn.execute(
                f"SELECT MAX(peak_memory_mb) FROM metrics "
                f"WHERE job_uuid IN ({marks})",
                job_uuids,
            ).fetchone()
        return float(row[0] or 0.0)

    # -- prometheus ingestion ----------------------------------------------

    # scraped-gauge base name -> JobMetricSample field. Covers both the
    # master-registry names (metrics_snapshot) and the agent-scrape
    # names so either side of the plane round-trips.
    GAUGE_FIELD_MAP = {
        "dlrover_job_steps_per_second": "steps_per_second",
        "dlrover_steps_per_second": "steps_per_second",
        "dlrover_job_tokens_per_second": "tokens_per_second",
        "dlrover_tokens_per_second": "tokens_per_second",
        "dlrover_job_peak_memory_mb": "peak_memory_mb",
        "dlrover_peak_memory_mb": "peak_memory_mb",
        "dlrover_cpu_percent": "cpu_percent",
        "dlrover_agent_world_size": "world_size",
        "dlrover_world_size": "world_size",
    }

    # how labeled series of one family combine into one sample value:
    # throughput sums across workers, memory takes the worst host,
    # utilization averages, world size is a max (every series reports
    # the same world; max tolerates a straggler's stale 0)
    _FIELD_AGG = {
        "steps_per_second": "sum",
        "tokens_per_second": "sum",
        "peak_memory_mb": "max",
        "cpu_percent": "mean",
        "world_size": "max",
    }

    def ingest_gauges(
        self,
        job_uuid: str,
        gauges: Dict[str, float],
        world_size: int = 0,
        timestamp: float = 0.0,
        field_map: Optional[Dict[str, str]] = None,
    ) -> Optional[JobMetricSample]:
        """Round-trip scraped metrics into one :class:`JobMetricSample`.

        Accepts the flattened key format ``parse_prometheus``
        (``agent/metric_collector.py``) emits: every sample keeps its
        FULL exposition key (``name{labels}``) and each labeled family
        additionally carries a bare-name alias holding its last
        sample. Keys are grouped by base name (the part before
        ``{``); when a family has labeled series, its bare alias is
        IGNORED — counting both would double the last worker's
        contribution. Per-field aggregation follows ``_FIELD_AGG``.

        Returns the stored sample, or None when no key mapped to a
        sample field (nothing is written).
        """
        fmap = field_map or self.GAUGE_FIELD_MAP
        series: Dict[str, List[float]] = {}
        has_labels: Dict[str, bool] = {}
        for key, value in gauges.items():
            base, brace, _ = key.partition("{")
            if base not in fmap:
                continue
            labeled = brace == "{"
            if labeled and not has_labels.get(base):
                # first labeled series wins the family: drop any bare
                # alias collected before it
                series[base] = []
                has_labels[base] = True
            elif not labeled and has_labels.get(base):
                continue  # bare alias of a labeled family
            series.setdefault(base, []).append(float(value))
        fields: Dict[str, float] = {}
        for base, values in series.items():
            if not values:
                continue
            name = fmap[base]
            agg = self._FIELD_AGG.get(name, "max")
            if agg == "sum":
                fields[name] = sum(values)
            elif agg == "mean":
                fields[name] = sum(values) / len(values)
            else:
                fields[name] = max(values)
        if not fields:
            return None
        sample = JobMetricSample(
            job_uuid=job_uuid,
            timestamp=timestamp or time.time(),
            world_size=world_size or int(fields.get("world_size", 0)),
            steps_per_second=fields.get("steps_per_second", 0.0),
            tokens_per_second=fields.get("tokens_per_second", 0.0),
            peak_memory_mb=fields.get("peak_memory_mb", 0.0),
            cpu_percent=fields.get("cpu_percent", 0.0),
        )
        self.add_metric(sample)
        return sample

    # -- events ------------------------------------------------------------

    def add_event(
        self, job_uuid: str, event_type: str, node_id: int = -1, detail: str = ""
    ) -> None:
        with self._mu:
            self._conn.execute(
                "INSERT INTO events VALUES (?,?,?,?,?)",
                (job_uuid, time.time(), event_type, node_id, detail),
            )
            self._conn.commit()

    def job_events(self, job_uuid: str, event_type: str = "") -> List[Dict]:
        q = "SELECT * FROM events WHERE job_uuid=?"
        args: List = [job_uuid]
        if event_type:
            q += " AND event_type=?"
            args.append(event_type)
        with self._mu:
            rows = self._conn.execute(q, args).fetchall()
        return [
            {
                "job_uuid": r[0],
                "timestamp": r[1],
                "event_type": r[2],
                "node_id": r[3],
                "detail": r[4],
            }
            for r in rows
        ]

    def close(self) -> None:
        with self._mu:
            self._conn.close()

    @staticmethod
    def _row_to_profile(row) -> JobProfile:
        return JobProfile(
            job_uuid=row[0],
            param_count=float(row[1] or 0.0),
            flops_per_step=float(row[2] or 0.0),
            tokens_per_batch=float(row[3] or 0.0),
            seq_len=int(row[4] or 0),
            arch=row[5] or "",
        )

    @staticmethod
    def _row_to_job(row) -> JobRecord:
        return JobRecord(
            job_uuid=row[0],
            job_name=row[1],
            model_signature=row[2],
            workload=row[3],
            worker_num=row[4],
            node_unit=row[5],
            status=row[6],
            created_at=row[7],
            finished_at=row[8],
            extra=json.loads(row[9] or "{}"),
        )


def job_record_to_dict(job: JobRecord) -> Dict:
    return asdict(job)

"""The one general traffic generator: a closed loop of ``clients`` uploaders
over a catalogue of datasets, read from a traffic file of parameters.

Every mix is the same rule with other numbers: set-up submits each catalogue
dataset once (``clients`` at a time; on a pool that places jobs over several
chips once per placement, ``Driver.each_placement``), so every shape the
window will use has compiled or loaded on every chip that will run it and the
residency LRU is in its steady state; the window
then goes on through the catalogue in cyclic order, each client waiting for
its report before its next submit: under a fresh ``ds_id`` each (a new
upload), or with ``"ds_id": "same"`` under the dataset's own (upstream's
reprocess; the residency cache keys a parsed dataset on its ds_id).  A catalogue of
one stays resident (``reannotate``); a catalogue larger than the LRU plus the
clients never hits (``uploads``).
"""

from __future__ import annotations

import shutil
import threading
import time
from pathlib import Path

from oracle import ASSIGNMENT
from serve import TERMINAL, Serve


def sizes(traffic: dict, chips: int) -> tuple[int, int]:
    """(clients, catalogue) of a mix on ``chips`` chips."""
    clients = traffic.get("clients") or traffic["clients_per_chip"] * chips
    catalogue = traffic.get("catalogue") or \
        traffic["catalogue_per_client"] * clients
    return int(clients), int(catalogue)


class Driver:
    """Submits jobs and watches them from the client's side: one poller
    thread reads ``GET /jobs`` every ``poll_ms`` and notes, on the client's
    clock, when a job first shows a non-empty ``partial`` and when it is
    terminal."""

    def __init__(self, serve: Serve, catalogue: list[dict], ds_config: dict,
                 prefix: str, poll_ms: float, same_ds_id: bool,
                 answers: Path):
        self.serve = serve
        self.catalogue = catalogue
        self.ds_config = ds_config
        self.prefix = prefix
        self.same_ds_id = same_ds_id
        self.answers = answers
        self.poll_s = poll_ms / 1000.0
        self.jobs: list[dict] = []
        self._lock = threading.Lock()
        self._next = 0
        self._stop = threading.Event()
        self._error: BaseException | None = None
        self._poller = threading.Thread(target=self._poll, daemon=True)
        self._poller.start()

    def close(self) -> None:
        self._stop.set()
        self._poller.join(timeout=30.0)

    def _poll(self) -> None:
        try:
            while not self._stop.is_set():
                with self._lock:
                    open_ = [j for j in self.jobs if not j["done"].is_set()]
                if open_:
                    rows = self.serve.jobs()
                    now = time.time()
                    for j in open_:
                        row = rows.get(j["msg_id"])
                        if row is None:
                            continue
                        if j["t_partial"] is None and row.get("partial"):
                            j["t_partial"] = now
                        if row["state"] in TERMINAL:
                            j["t_end"], j["row"] = now, row
                            j["done"].set()
                    self.serve.alive()
                self._stop.wait(self.poll_s)
        except BaseException as exc:       # surfaced by the waiting client
            self._error = exc
            with self._lock:
                for j in self.jobs:
                    j["done"].set()

    def submit(self, k: int | None = None) -> dict:
        """The next dataset of the cyclic order (or dataset ``k``, for
        set-up), under a fresh ds_id."""
        with self._lock:
            n = self._next
            self._next += 1
        if k is None:
            k = n % len(self.catalogue)
        ds = self.catalogue[k]
        msg_id = f"{self.prefix}-{n:04d}"
        # a new upload gets a fresh ds_id; a reprocess keeps its dataset's
        ds_id = f"{self.prefix}-ds{k}" if self.same_ds_id else msg_id
        job = {"msg_id": msg_id, "ds_id": ds_id, "n": n, "dataset": ds,
               "t_partial": None, "t_end": None, "row": None,
               "done": threading.Event(), "t_submit": time.time()}
        with self._lock:
            self.jobs.append(job)
        self.serve.submit({"ds_id": ds_id, "msg_id": msg_id,
                           "input_path": ds["path"],
                           "formulas": ds["formulas"],
                           "ds_config": self.ds_config})
        return job

    def wait(self, job: dict, until: float) -> bool:
        """True when ``job`` is terminal by wall time ``until``.  The client
        then keeps the stored answer under the job's own name, as a user who
        downloads the report would: a reprocess of the same ds_id overwrites
        ``results/<ds_id>`` with the next job's."""
        ok = job["done"].wait(timeout=max(0.0, until - time.time()))
        if self._error is not None:
            raise self._error
        if ok and job["row"]["state"] == "done":
            kept = self.answers / job["msg_id"]
            kept.mkdir(parents=True)
            stored = self.serve.results / job["ds_id"]
            for name in ("all_metrics.parquet", "annotations.parquet"):
                shutil.copy(stored / name, kept)
            # the decoy assignment, where the job stored one (README.md:
            # required only with more than one target adduct)
            if (stored / ASSIGNMENT).exists():
                shutil.copy(stored / ASSIGNMENT, kept)
        return ok

    def each_placement(self, placements: int, lease_of, go_on,
                       tries: int = 3,
                       job_timeout: float = 900.0) -> tuple[list, int]:
        """Set-up on a pool that places jobs over several chips: every
        catalogue dataset ``placements`` times at once and alone in the
        pool, which leases the free chips of lowest index, so that one copy
        lands on each placement.  The program compiles, caches and loads an
        executable per chip, and a dataset's capacities pick its
        executables: a dataset a chip has not scored yet costs that chip a
        compile (~30 s under the lease) or a load inside the window.
        ``lease_of(job)`` says which chips a finished job was leased; a
        dataset that missed a placement goes round again, ``tries`` times in
        all.  After the first, a round starts only while ``go_on()`` says so
        (a run has a time limit; what is left then is counted, not hidden).
        Returns the wall
        seconds of each round and how many (dataset, placement) pairs no job
        covered."""
        walls, missing = [], 0
        for k in range(len(self.catalogue)):
            seen: set[tuple] = set()
            for _ in range(tries):
                if walls and not go_on():
                    break
                t0 = time.time()
                jobs = [self.submit(k) for _ in range(placements)]
                for job in jobs:
                    if not self.wait(job, time.time() + job_timeout):
                        raise RuntimeError(
                            f"job {job['msg_id']} not terminal after "
                            f"{job_timeout:.0f}s")
                walls.append(round(time.time() - t0, 1))
                seen |= {tuple(lease_of(job)) for job in jobs}
                if len(seen) >= placements:
                    break
            missing += max(0, placements - len(seen))
        return walls, missing

    def run(self, clients: int, until: float | None = None,
            count: int | None = None, job_timeout: float = 900.0) -> None:
        """``clients`` closed loops: until wall time ``until`` (the window:
        a job in flight then is left behind), or ``count`` jobs in all
        (set-up: every one is waited for)."""
        errors: list[BaseException] = []
        budget = threading.Semaphore(count) if count is not None else None

        def loop():
            try:
                while True:
                    if until is not None and time.time() >= until:
                        return
                    if budget is not None and not budget.acquire(
                            blocking=False):
                        return
                    job = self.submit()
                    end = until if until is not None else \
                        time.time() + job_timeout
                    if not self.wait(job, end):
                        if until is None:
                            raise RuntimeError(
                                f"job {job['msg_id']} not terminal after "
                                f"{job_timeout:.0f}s")
                        return
            except BaseException as exc:
                errors.append(exc)

        threads = [threading.Thread(target=loop) for _ in range(clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if errors:
            raise errors[0]

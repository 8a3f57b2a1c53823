"""Traffic kind ``retrain_job``: a unit is one whole job on host arrays
already in memory: device data set, fresh estimator, ``prepare`` (plan,
pack, transfer), ``fit``, ``save_game_model``. Set-up runs whole jobs, so
that no compile falls into the window."""

import gc
import os
import shutil
import tempfile

from benchmark import sut


class Kind:
    unit_name = "jobs"

    def __init__(self, config: dict, traffic: dict, data, spans):
        self.config, self.traffic, self.data = config, traffic, data
        self.spans = spans
        self.plan_shapes: dict = {}
        self.jobs: list[dict] = []
        self.last_model = None
        self.tmp = tempfile.mkdtemp(prefix="photon_bench_")
        self.saved_dir = None

    def _job(self, k) -> None:
        before = sut.compile_counters()
        with self.spans.span("bench.dataset"):
            # The job before this one still holds its device arrays.
            self.last_model = None
            gc.collect()
            dataset = sut.build_dataset(self.data)
        with self.spans.span("bench.prepare"):
            est = sut.build_estimator(self.config)
            datasets, _ = est.prepare(dataset)
        with self.spans.span("bench.fit"):
            result = sut.fit_blocking(est, dataset)
        pipeline = sut.pipeline_report()
        with self.spans.span("bench.save"):
            out = os.path.join(self.tmp, f"job_{k}")
            sut.save_model(result.model, self.config, out)
        if self.saved_dir is not None:
            shutil.rmtree(self.saved_dir, ignore_errors=True)
        self.saved_dir = out
        self.plan_shapes = sut.plan_shapes(datasets)
        self.last_model = result.model
        after = sut.compile_counters()
        self.jobs.append({
            "pipeline": pipeline,
            "cache_loads": after["hits"] - before["hits"],
            "compiles": after["misses"] - before["misses"],
        })

    def setup(self) -> None:
        for k in range(int(self.traffic["warmup_jobs"])):
            self._job(f"warmup{k}")
        self.jobs.clear()

    def unit(self, k: int) -> None:
        self._job(k)

    def end_to_end(self, units: int, window_s: float) -> dict:
        return {"retrain_s": window_s / units}

    def report(self) -> dict:
        return {"plan_shapes": dict(self.plan_shapes),
                "jobs": list(self.jobs)}

    def answer(self) -> dict:
        """The last job's model as read back from what it saved, and the
        model it held in memory beside it."""
        return {
            "tables": sut.load_model_tables(self.config, self.saved_dir),
            "saved_from": sut.model_tables(self.last_model, self.config),
        }

    def release(self) -> None:
        self.last_model = None
        shutil.rmtree(self.tmp, ignore_errors=True)
        gc.collect()

"""Traffic kind ``refit``: set-up prepares once and fits once; a unit is
one whole warm fit on the prepared, device-resident data."""

import gc

from benchmark import sut


class Kind:
    unit_name = "fits"

    def __init__(self, config: dict, traffic: dict, data, spans):
        self.config, self.traffic, self.data = config, traffic, data
        self.spans = spans
        self.first = self.last = self.last_result = None
        self.plan_shapes: dict = {}

    def setup(self) -> None:
        self.dataset = sut.build_dataset(self.data)
        self.est = sut.build_estimator(self.config)
        datasets, _ = self.est.prepare(self.dataset)
        self.plan_shapes = sut.plan_shapes(datasets)
        for _ in range(int(self.traffic["warmup_fits"])):
            sut.fit_blocking(self.est, self.dataset)

    def unit(self, k: int) -> None:
        with self.spans.span("bench.fit"):
            result = sut.fit_blocking(self.est, self.dataset)
        if k == 0:
            self.first = result.model
        self.last, self.last_result = result.model, result

    def end_to_end(self, units: int, window_s: float) -> dict:
        swept = (float(self.config["rows"])
                 * float(self.config["num_iterations"]) * units)
        return {"train_rows_per_s": swept / window_s}

    def report(self) -> dict:
        return {"plan_shapes": dict(self.plan_shapes),
                "fixed_iterations": sut.fixed_effect_iterations(
                    self.last_result)}

    def answer(self) -> dict:
        """The last fit of the window, and the first beside it: every fit
        of the window solves the same problem, so they have to agree."""
        return {
            "tables": sut.model_tables(self.last, self.config),
            "repeat_of": sut.model_tables(self.first, self.config),
        }

    def release(self) -> None:
        self.dataset = self.est = self.first = self.last = None
        self.last_result = None
        gc.collect()

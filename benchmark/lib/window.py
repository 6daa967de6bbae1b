"""The measured window, cut out of one ``train()`` call.

``StandardWorkflow.train`` runs epochs, not seconds.  Every epoch it
calls ``workflow.metrics_writer.write(kind="epoch", ...)`` and then
reads ``decision.fail_iterations``; :class:`EpochClock` stands in for
the metrics writer, takes the host clock there (every device call of the
epoch has been read back by then), and ends the run by setting
``fail_iterations`` to 0 once the window is full."""

from __future__ import annotations

import time


class EpochClock:
    """Warm-up = the first ``warm_epochs`` epochs; in a traced run the
    next ``traced_epochs`` are traced and not timed; the window is from
    there to the end of the first epoch that ends ``seconds`` later."""

    def __init__(self, workflow, *, seconds: float, warm_epochs: int,
                 recorder, tracer=None, traced_epochs: int = 0,
                 on_trace_start=None, on_window_start=None):
        self.workflow = workflow
        self.seconds = float(seconds)
        self.warm_epochs = int(warm_epochs)
        self.recorder = recorder
        self.tracer = tracer
        self.traced_epochs = int(traced_epochs) if tracer else 0
        self.on_trace_start = on_trace_start
        self.on_window_start = on_window_start
        self.ends: list[float] = []        # host clock at each epoch's end
        self.rows: list[dict] = []         # the program's timeline rows
        self.window_start: int | None = None   # index into ends

    def write(self, kind: str = "epoch", **metrics) -> None:
        if kind != "epoch":
            return
        now = time.monotonic()
        self.ends.append(now)
        recent = self.recorder.snapshot(n=1)["recent"]
        self.rows.append(dict(recent[-1]) if recent else {})
        done = len(self.ends)
        trace_from = self.warm_epochs
        if self.tracer is not None and done == trace_from:
            if self.on_trace_start is not None:
                self.on_trace_start()
            self.tracer.start()
            self.ends[-1] = time.monotonic()
        if done == trace_from + self.traced_epochs:
            if self.tracer is not None:
                self.tracer.stop()
                self.ends[-1] = time.monotonic()
            self.window_start = done - 1
            if self.on_window_start is not None:
                self.on_window_start()
        elif (self.window_start is not None
              and self.ends[-1] - self.ends[self.window_start]
              >= self.seconds):
            self.workflow.decision.fail_iterations = 0

    # -- what the window held ---------------------------------------------
    @property
    def window_s(self) -> float:
        return self.ends[-1] - self.ends[self.window_start]

    @property
    def window_epochs(self) -> int:
        return len(self.ends) - 1 - self.window_start

    def window_rows(self) -> list[dict]:
        return self.rows[self.window_start + 1:]

    def epoch_walls_s(self) -> list[float]:
        e = self.ends[self.window_start:]
        return [b - a for a, b in zip(e, e[1:])]

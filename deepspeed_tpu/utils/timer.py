"""Wall-clock timers and throughput accounting.

TPU-native rework of the reference's ``deepspeed/utils/timer.py``
(``SynchronizedWallClockTimer`` :33, ``ThroughputTimer`` :137). CUDA events do
not exist here; device-synchronized timing is done by blocking on
``jax.block_until_ready`` at timer boundaries when ``synchronized=True``.
"""

import time

from deepspeed_tpu.utils.logging import log_dist

FORWARD_MICRO_TIMER = "fwd_microstep"
FORWARD_GLOBAL_TIMER = "fwd"
BACKWARD_MICRO_TIMER = "bwd_microstep"
BACKWARD_GLOBAL_TIMER = "bwd"
STEP_MICRO_TIMER = "step_microstep"
STEP_GLOBAL_TIMER = "step"


def _sync():
    try:
        import jax
        (jax.device_put(0.0) + 0).block_until_ready()
    except Exception:
        pass


class Timer:
    """A single named timer supporting repeated start/stop accumulation."""

    def __init__(self, name, synchronized=False):
        self.name = name
        self.synchronized = synchronized
        self.started = False
        self.start_time = 0.0
        self.elapsed_ = 0.0
        self.count = 0

    def start(self):
        if self.started:
            return
        if self.synchronized:
            _sync()
        self.start_time = time.time()
        self.started = True

    def stop(self, record=True):
        if not self.started:
            return
        if self.synchronized:
            _sync()
        self.elapsed_ += time.time() - self.start_time
        self.count += 1
        self.started = False

    def reset(self):
        self.started = False
        self.elapsed_ = 0.0
        self.count = 0

    def elapsed(self, reset=True):
        elapsed = self.elapsed_
        if self.started:
            elapsed += time.time() - self.start_time
        if reset:
            self.reset()
        return elapsed

    def mean(self):
        return self.elapsed_ / max(1, self.count)


class SynchronizedWallClockTimer:
    """Group of named timers (reference: utils/timer.py:33)."""

    def __init__(self, synchronized=True):
        self.timers = {}
        self.synchronized = synchronized

    def __call__(self, name):
        if name not in self.timers:
            self.timers[name] = Timer(name, synchronized=self.synchronized)
        return self.timers[name]

    @staticmethod
    def memory_usage():
        try:
            import jax
            stats = jax.local_devices()[0].memory_stats() or {}
            in_use = stats.get("bytes_in_use", 0) / (1024**3)
            peak = stats.get("peak_bytes_in_use", 0) / (1024**3)
            return f"DeviceMem: in_use {in_use:.2f} GB, peak {peak:.2f} GB"
        except Exception:
            return "DeviceMem: unavailable"

    def log(self, names, normalizer=1.0, reset=True, memory_breakdown=False, ranks=None):
        assert normalizer > 0.0
        string = "time (ms)"
        for name in names:
            if name in self.timers:
                elapsed = self.timers[name].elapsed(reset=reset) * 1000.0 / normalizer
                string += f" | {name}: {elapsed:.2f}"
        if memory_breakdown:
            string += " | " + self.memory_usage()
        log_dist(string, ranks=ranks or [0])

    def get_timers(self):
        return self.timers


class NoopTimer:
    class _Inner:
        def start(self):
            pass

        def stop(self, **kwargs):
            pass

        def reset(self):
            pass

        def elapsed(self, **kwargs):
            return 0.0

    def __call__(self, name):
        return self._Inner()

    def log(self, *args, **kwargs):
        pass

    def get_timers(self):
        return {}


class ThroughputTimer:
    """Samples/sec + TFLOPs accounting (reference: utils/timer.py:137).

    With a ``monitor`` whose ``enabled`` flag is truthy, the periodic
    report rides the monitor event stream (``train/samples_per_s`` +
    ``train/samples_per_s_avg``, stepped by global step) instead of the
    bare ``log_dist`` print — same cadence, same numbers, one telemetry
    surface (docs/observability.md taxonomy).  Without one (or with a
    disabled MonitorMaster) the legacy print is preserved byte-for-byte.
    """

    def __init__(self, batch_size, start_step=2, steps_per_output=50,
                 monitor_memory=False, monitor=None,
                 event_prefix="train/"):
        self.monitor = monitor
        self.event_prefix = event_prefix
        self.start_time = 0
        self.end_time = 0
        self.started = False
        self.batch_size = max(1, batch_size)
        self.start_step = start_step
        self.epoch_count = 0
        self.micro_step_count = 0
        self.global_step_count = 0
        self.total_elapsed_time = 0
        self.step_elapsed_time = 0
        self.steps_per_output = steps_per_output
        self.monitor_memory = monitor_memory
        self._steps_since_report = 0

    def update_epoch_count(self):
        self.epoch_count += 1
        self.micro_step_count = 0

    def start(self):
        self.started = True
        if self.global_step_count >= self.start_step:
            # sync only at a measurement-window edge: a device barrier
            # per step would serialize the async dispatch queue (a
            # full host round trip per step);
            # per-step wall deltas still sum to the true window time
            if self.global_step_count == self.start_step:
                _sync()
            self.start_time = time.time()

    def stop(self, global_step=False, report_speed=True, steps=1):
        """``steps`` > 1 credits one start/stop span with that many
        optimizer steps (train_loop's fused multi-step dispatch), keeping
        samples/sec and step-count-driven reporting honest."""
        if not self.started:
            return
        self.started = False
        self.micro_step_count += steps
        if global_step:
            self.global_step_count += steps
        if self.start_time > 0:
            if global_step and \
                    self.global_step_count % self.steps_per_output == 0:
                _sync()
            self.end_time = time.time()
            duration = self.end_time - self.start_time
            self.total_elapsed_time += duration
            self.step_elapsed_time += duration
            self.start_time = 0
            if global_step:
                self._steps_since_report += steps
                if report_speed and \
                        self.global_step_count % self.steps_per_output == 0:
                    # current rate over the whole window since the last
                    # report: with sync only at window edges, a single
                    # step's delta would absorb the async queue drain
                    window = self.batch_size * self._steps_since_report
                    curr = window / self.step_elapsed_time
                    avg = self.avg_samples_per_sec()
                    if self.monitor is not None and \
                            getattr(self.monitor, "enabled", True):
                        events = [(self.event_prefix + "samples_per_s",
                                   float(curr), self.global_step_count)]
                        if avg > float("-inf"):
                            events.append(
                                (self.event_prefix + "samples_per_s_avg",
                                 float(avg), self.global_step_count))
                        self.monitor.write_events(events)
                    else:
                        log_dist(
                            f"epoch={self.epoch_count}/micro_step={self.micro_step_count}/"
                            f"global_step={self.global_step_count}, "
                            f"RunningAvgSamplesPerSec={avg:.4f}, "
                            f"CurrSamplesPerSec={curr:.4f}",
                            ranks=[0])
                    self.step_elapsed_time = 0
                    self._steps_since_report = 0

    def avg_samples_per_sec(self):
        if self.global_step_count > self.start_step and self.total_elapsed_time > 0:
            samples = self.batch_size * (self.global_step_count - self.start_step)
            return samples / self.total_elapsed_time
        return float("-inf")

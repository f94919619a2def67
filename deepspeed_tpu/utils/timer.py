"""Wall-clock + throughput timers — rebuild of deepspeed/utils/timer.py:19,97.

The reference synchronizes CUDA before reading the clock; here we call
``jax.block_until_ready``-style synchronization via
``jax.effects_barrier``/device sync only when asked, since under jit the
dispatch is async.
"""

import time

from deepspeed_tpu.utils.logging import logger


_sync_token = None


def _sync_device():
    """Block until previously dispatched work is done — the TPU analog of
    torch.cuda.synchronize(). Enqueues one cached tiny computation behind the
    in-flight work and waits on it (cached, so a sync costs no host→device
    transfer)."""
    global _sync_token
    try:
        import jax
        if _sync_token is None:
            import jax.numpy as jnp
            _sync_token = jax.jit(lambda: jnp.zeros((), jnp.int32))
        _sync_token().block_until_ready()
    except Exception:
        pass


class SynchronizedWallClockTimer:
    """Named timer group; ``elapsed`` synchronizes the device first."""

    class Timer:
        def __init__(self, name):
            self.name_ = name
            self.elapsed_ = 0.0
            self.started_ = False
            self.start_time = time.time()

        def start(self, sync=True):
            assert not self.started_, f"{self.name_} timer has already been started"
            if sync:
                _sync_device()
            self.start_time = time.time()
            self.started_ = True

        def stop(self, sync=True, reset=False):
            assert self.started_, "timer is not started"
            if sync:
                _sync_device()
            if reset:
                self.elapsed_ = time.time() - self.start_time
            else:
                self.elapsed_ += time.time() - self.start_time
            self.started_ = False

        def reset(self):
            self.elapsed_ = 0.0
            self.started_ = False

        def elapsed(self, reset=True):
            started_ = self.started_
            if self.started_:
                self.stop()
            elapsed_ = self.elapsed_
            if reset:
                self.reset()
            if started_:
                self.start()
            return elapsed_

        def mean(self, count):
            return self.elapsed(reset=False) / max(count, 1)

    def __init__(self):
        self.timers = {}

    def __call__(self, name):
        if name not in self.timers:
            self.timers[name] = self.Timer(name)
        return self.timers[name]

    def log(self, names, normalizer=1.0, reset=True, memory_breakdown=False):
        assert normalizer > 0.0
        string = "time (ms)"
        for name in names:
            if name in self.timers:
                elapsed_time = self.timers[name].elapsed(reset=reset) * 1000.0 / normalizer
                string += " | {}: {:.2f}".format(name, elapsed_time)
        logger.info(string)


class ThroughputTimer:
    """Samples/sec reporting — reference utils/timer.py:97, used by the engine
    for per-step throughput lines (engine.py:176-180)."""

    def __init__(self,
                 batch_size,
                 num_workers=1,
                 start_step=2,
                 steps_per_output=50,
                 monitor_memory=False,
                 logging_fn=None):
        self.start_time = 0
        self.end_time = 0
        self.started = False
        self.batch_size = max(batch_size, 1)
        self.num_workers = num_workers
        self.start_step = start_step
        self.epoch_count = 0
        self.local_step_count = 0
        self.total_step_count = 0
        self.total_elapsed_time = 0
        self.steps_per_output = steps_per_output
        self.monitor_memory = monitor_memory
        self.logging = logging_fn or logger.info
        self.initialized = False

    def update_epoch_count(self):
        self.epoch_count += 1
        self.local_step_count = 0

    def _init_timer(self):
        self.initialized = True

    def start(self):
        self._init_timer()
        self.started = True
        if self.total_step_count == self.start_step:
            # timeline accounting: sync once at the start of the measured
            # region, then measure contiguous wall time window-by-window.
            # Syncing every step would serialize dispatch against execution;
            # skipping sync but summing per-step gaps would silently drop
            # device work that runs during host-side gaps. Wall-clock windows
            # bounded by syncs count everything exactly once.
            _sync_device()
            self._window_start = time.time()
            self._steps_in_windows = 0

    def _fold_window(self):
        """Close the current window: sync, add its wall time, start a new
        window."""
        _sync_device()
        now = time.time()
        self.total_elapsed_time += now - self._window_start
        self._steps_in_windows = self.total_step_count - self.start_step
        self._window_start = now

    def stop(self, report_speed=True):
        if not self.started:
            return
        self.started = False
        self.total_step_count += 1
        self.local_step_count += 1
        if self.total_step_count > self.start_step:
            self.end_time = time.time()
            if report_speed and \
                    self.local_step_count % self.steps_per_output == 0:
                self._fold_window()
                self.logging(
                    "{}/{}, SamplesPerSec={}".format(self.epoch_count,
                                                     self.local_step_count,
                                                     self.avg_samples_per_sec()))

    def avg_samples_per_sec(self, fold=False):
        if self.total_step_count > self.start_step:
            if fold or not getattr(self, "_steps_in_windows", 0):
                self._fold_window()
            steps = max(getattr(self, "_steps_in_windows", 0), 1)
            samples_per_step = self.batch_size * self.num_workers
            avg_time_per_step = self.total_elapsed_time / steps
            return samples_per_step / max(avg_time_per_step, 1e-12)
        return float("-inf")

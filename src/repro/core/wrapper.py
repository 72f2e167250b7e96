"""MCT Wrapper — the paper's multi-threaded Host-Executor (§4.1).

Round-robin dealer over worker threads; each worker encodes its batch
(pipelined with the previous batch's kernel execution), dispatches to an
engine lane, and collects/partitions results back per Travel Solution.
Every stage is timed (paper Fig. 6 decomposition):

  queue -> encode -> dispatch (host->device) -> kernel -> collect

``StageTimes`` stamps each stage on the host clock; ``dispatch_us`` (the
engine's padding of the rows and their host-to-device copy) and
``compile_us`` (the match call's time when it compiled) are read from the
engine and kept out of ``kernel_us``. Each stage is also a
``jax.profiler`` span on the device trace's clock (names in
``repro.serve.trace.EXECUTOR_STAGES``): ``mct.execute`` over a worker's
whole batch (args ``checks``, ``worker``; queue wait is outside it), and
inside it ``mct.encode``, ``mct.device_execute`` (the match until its
results are ready, holding the engine's ``mct.dispatch`` and, where the
call compiled, ``mct.compile``) and ``mct.collect`` (the device-to-host
copies). The spans cost about a microsecond each with no profiler
running.
"""
from __future__ import annotations

import dataclasses
import queue
import threading
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence

import jax
import numpy as np

from repro.core.aggregator import Batch
from repro.core.encoder import queries_to_arrays
from repro.core.engine import ErbiumEngine
from repro.serve.trace import EXECUTOR_SPANS

_ann = jax.profiler.TraceAnnotation


@dataclass
class StageTimes:
    queue_us: float = 0.0
    encode_us: float = 0.0
    dispatch_us: float = 0.0
    kernel_us: float = 0.0
    collect_us: float = 0.0
    batch: int = 0
    compile_us: float = 0.0

    @property
    def total_us(self) -> float:
        return (self.queue_us + self.encode_us + self.dispatch_us +
                self.compile_us + self.kernel_us + self.collect_us)


@dataclass
class MCTResult:
    uid: int
    decisions: np.ndarray
    weights: np.ndarray
    times: StageTimes


class MCTWrapper:
    """n_workers worker threads sharing one engine pool (1..k engines)."""

    def __init__(self, engines: Sequence[ErbiumEngine], n_workers: int = 1):
        self.engines = list(engines)
        self.n_workers = n_workers
        self._in: "queue.Queue" = queue.Queue()
        self._out: "queue.Queue" = queue.Queue()
        self._threads: List[threading.Thread] = []
        self._stop = threading.Event()
        self._rr = 0
        self._local = threading.local()    # .worker: this thread's index

    # -- lifecycle -----------------------------------------------------------
    def start(self):
        for wi in range(self.n_workers):
            t = threading.Thread(target=self._worker_loop,
                                 args=(wi,), daemon=True)
            t.start()
            self._threads.append(t)

    def stop(self):
        self._stop.set()
        for _ in self._threads:
            self._in.put(None)
        for t in self._threads:
            t.join(timeout=5)
        self._threads.clear()
        self._stop.clear()

    # -- request path --------------------------------------------------------
    def submit(self, batch: Batch):
        self._in.put((time.perf_counter(), batch))

    def drain(self, n: int, timeout: float = 60.0) -> List[MCTResult]:
        out = []
        for _ in range(n):
            out.append(self._out.get(timeout=timeout))
        return out

    def process(self, batch: Batch, engine_idx: int = 0) -> MCTResult:
        """Synchronous single-request path (used for stage benchmarking)."""
        return self._execute(time.perf_counter(), batch, engine_idx)

    # -- internals ------------------------------------------------------------
    def _worker_loop(self, wi: int):
        self._local.worker = wi
        while not self._stop.is_set():
            item = self._in.get()
            if item is None:
                return
            t_in, batch = item
            eng = wi % len(self.engines)
            self._out.put(self._execute(t_in, batch, eng))

    def _execute(self, t_in: float, batch: Batch, eng_idx: int) -> MCTResult:
        st = StageTimes(batch=len(batch.queries))
        eng = self.engines[eng_idx]
        t0 = time.perf_counter()
        st.queue_us = (t0 - t_in) * 1e6
        with _ann(EXECUTOR_SPANS["execute"], checks=st.batch,
                  worker=getattr(self._local, "worker", -1)):
            with _ann(EXECUTOR_SPANS["encode"]):
                fields = queries_to_arrays(batch.queries)
                enc = eng.encode(fields)
            t1 = time.perf_counter()
            st.encode_us = (t1 - t0) * 1e6

            with _ann(EXECUTOR_SPANS["device_execute"]):
                dec, w, rid = eng.match(enc)
                jax.block_until_ready((dec, w, rid))
            t3 = time.perf_counter()
            st.dispatch_us = eng.last_dispatch_s() * 1e6
            st.compile_us = eng.last_compile_s() * 1e6
            st.kernel_us = (t3 - t1) * 1e6 - st.dispatch_us - st.compile_us

            with _ann(EXECUTOR_SPANS["collect"]):
                dec_h = np.asarray(dec)
                w_h = np.asarray(w)
            st.collect_us = (time.perf_counter() - t3) * 1e6
        return MCTResult(uid=batch.uid, decisions=dec_h, weights=w_h,
                         times=st)


def measure_stage_times(engine: ErbiumEngine, make_batch, batch_sizes,
                        repeats: int = 3) -> List[StageTimes]:
    """Fig-6 style stage decomposition over batch sizes (median of repeats).
    ``make_batch(n)`` returns a Batch with n queries."""
    wrap = MCTWrapper([engine], n_workers=1)
    out = []
    for n in batch_sizes:
        b = make_batch(n)
        wrap.process(b)  # warmup (jit compile)
        runs = [wrap.process(b).times for _ in range(repeats)]
        med = StageTimes(
            batch=n,
            queue_us=float(np.median([r.queue_us for r in runs])),
            encode_us=float(np.median([r.encode_us for r in runs])),
            dispatch_us=float(np.median([r.dispatch_us for r in runs])),
            kernel_us=float(np.median([r.kernel_us for r in runs])),
            collect_us=float(np.median([r.collect_us for r in runs])),
            compile_us=float(np.median([r.compile_us for r in runs])))
        out.append(med)
    return out

"""The one traffic generator.  Every mix is a data file, ``bench/traffic/<mix>.json``.

Two loops read a mix:

* ``"loop": "closed"`` -- callers that each wait for their answer: the
  loop keeps ``in_flight`` requests outstanding and sends the next one
  when one resolves.  It stops sending only when the count it has sent
  is a multiple of the bucket cap, so every bucket it forms is full and
  no bucket size but the cap is ever dispatched.  The window opens when
  the first bucket has been answered in full, with the pipeline full.
  When its time is up the loop sends nothing more (past the bucket it
  has begun), waits for every answer, and closes the window after the
  last: all of that work counts, over all of that time.
* ``"loop": "open"`` -- independent sources on a schedule: ``streams``
  sources each send one request every ``period_s`` seconds from a phase
  drawn from the seed, whether or not earlier answers came back.  Each
  request is timed from the moment it was due.

Payloads are made by :func:`make_pool` from the configuration's ``data``
entry and the seed; requests cycle through a pool of ``pool`` payloads.
Which answers are kept for the comparison is drawn from the seed too:
each request with probability ``1 / check_every``.
"""

from __future__ import annotations

import dataclasses
import math
import queue
import time
from concurrent.futures import CancelledError
from functools import partial
from typing import Optional

import numpy as np


def make_pool(data: dict, s: int, count: int, rng) -> np.ndarray:
    """``count`` request payloads of length ``s`` from the config's ``data``.

    * ``complex_gaussian``: complex64 samples, real and imaginary parts
      standard normal (voltages of a noise-dominated radio band);
    * ``audio_frames``: consecutive frames, ``hop_length`` apart, of one
      seeded audio signal at ``sr`` (a few tones in noise), each times a
      periodic Hann window of length ``s``, as ``librosa.stft`` frames.
    """
    kind = data["type"]
    if kind == "complex_gaussian":
        z = rng.standard_normal((count, 2 * s), dtype=np.float32)
        return z.view(np.complex64)
    if kind == "audio_frames":
        hop, sr = int(data["hop_length"]), float(data["sr"])
        t = np.arange((count - 1) * hop + s) / sr
        tones = data.get("tones", 8)
        freqs = rng.uniform(40.0, 0.45 * sr, tones)
        amps = rng.uniform(0.05, 0.5, tones)
        phases = rng.uniform(0.0, 2.0 * np.pi, tones)
        audio = 0.1 * rng.standard_normal(t.size)
        for f, a, p in zip(freqs, amps, phases):
            audio += a * np.sin(2.0 * np.pi * f * t + p)
        window = 0.5 - 0.5 * np.cos(2.0 * np.pi * np.arange(s) / s)
        frames = np.lib.stride_tricks.sliding_window_view(audio, s)[::hop]
        return (frames[:count] * window).astype(np.float32)
    raise ValueError(f"unknown payload type {kind!r}")


@dataclasses.dataclass
class Record:
    """What one loop did, request by request (host ``perf_counter`` times)."""

    due: np.ndarray        # when each request was due (closed: when sent)
    sent: np.ndarray       # when it was handed to submit()
    done: np.ndarray       # when its future resolved (NaN: never)
    failed: np.ndarray     # raised, was refused, or never resolved
    refused: np.ndarray    # of those, refused by admission control
    pool_idx: np.ndarray   # which payload it carried
    samples: dict          # request index -> answer kept for the check
    t_open: float          # the measured window
    t_close: float
    gave_up: float         # when the loop stopped waiting for answers

    @property
    def n(self) -> int:
        return int(self.due.size)


class _Ledger:
    """Per-request arrays that grow as a closed loop sends."""

    def __init__(self, cap: int):
        self.due = np.full(cap, np.nan)
        self.sent = np.full(cap, np.nan)
        self.done = np.full(cap, np.nan)
        self.failed = np.zeros(cap, bool)
        self.refused = np.zeros(cap, bool)
        self.pool_idx = np.zeros(cap, np.int64)

    def grow(self, j: int) -> None:
        if j < self.due.size:
            return
        for name in ("due", "sent", "done", "failed", "refused", "pool_idx"):
            old = getattr(self, name)
            fill = np.nan if old.dtype.kind == "f" else 0
            new = np.full(2 * old.size, fill, dtype=old.dtype)
            new[:old.size] = old
            setattr(self, name, new)

    def record(self, n: int, samples: dict, t_open, t_close, gave_up
               ) -> Record:
        return Record(self.due[:n], self.sent[:n], self.done[:n],
                      self.failed[:n], self.refused[:n], self.pool_idx[:n],
                      samples, t_open, t_close, gave_up)


def _kept(fut) -> tuple[bool, Optional[np.ndarray]]:
    """(failed, answer) of a resolved future."""
    try:
        return False, fut.result()
    except (CancelledError, Exception):          # noqa: BLE001
        return True, None


def closed_loop(stream, pool, kind: str, tier: str, mix: dict,
                seconds: float, cap: int, keep: np.ndarray, hooks,
                wait_s: float = 60.0) -> Record:
    from repro.serving.streaming import AdmissionError

    in_flight = int(mix["in_flight"])
    if in_flight % cap:
        raise ValueError(f"in_flight={in_flight} is not a multiple of the "
                         f"bucket cap {cap}")
    led = _Ledger(4096)
    samples: dict = {}
    answers: queue.SimpleQueue = queue.SimpleQueue()

    def on_done(j, fut):
        t = time.perf_counter()
        failed, out = _kept(fut)
        if not failed and keep[j % keep.size]:
            samples[j] = np.array(out, copy=True)
        answers.put((j, t, failed))

    def send(j):
        led.grow(j)
        idx = j % len(pool)
        led.pool_idx[j] = idx
        led.due[j] = led.sent[j] = time.perf_counter()
        try:
            fut = stream.submit(pool[idx], kind=kind, tier=tier)
        except AdmissionError:
            led.refused[j] = True
            answers.put((j, time.perf_counter(), True))
            return
        fut.add_done_callback(partial(on_done, j))

    for j in range(in_flight):
        send(j)
    n, answered = in_flight, 0
    t_open = t_stop = None
    gave_up = math.inf
    while answered < n:
        now = time.perf_counter()
        if t_open is not None and t_stop is None and now >= t_open + seconds:
            t_stop = now                          # time is up: drain
            gave_up = now + wait_s
        if t_stop is None:
            timeout = wait_s if t_open is None else t_open + seconds - now
        else:
            timeout = gave_up - now
        try:
            j, t, failed = answers.get(timeout=None if timeout is None
                                       else max(timeout, 0.0))
        except queue.Empty:
            if t_open is None:
                raise RuntimeError(f"no bucket was answered in full within "
                                   f"{wait_s} s") from None
            if t_stop is None:
                continue
            break                                 # answers that never came
        led.done[j] = t
        led.failed[j] = failed
        answered += 1
        if t_open is None and answered == cap:
            t_open = t
            hooks.open(t)
        if t_stop is None or n % cap:
            send(n)
            n += 1
    t_close = gave_up = time.perf_counter()
    hooks.close(t_close)
    unanswered = np.isnan(led.done[:n])
    led.failed[:n] |= unanswered
    return led.record(n, samples, t_open, t_close, gave_up)


def open_schedule(mix: dict, seconds: float, pool_size: int, rng
                  ) -> tuple[np.ndarray, np.ndarray]:
    """Due offsets (sorted, seconds from the window's start) and the
    payload of each request: stream ``i`` sends consecutive frames of the
    pool from a start drawn from the seed."""
    period, streams = float(mix["period_s"]), int(mix["streams"])
    phases = rng.uniform(0.0, period, streams)
    starts = rng.integers(0, pool_size, streams)
    k = np.arange(int(math.ceil(seconds / period)) + 1)
    due = phases[:, None] + period * k[None, :]
    sid = np.broadcast_to(np.arange(streams)[:, None], due.shape)
    kk = np.broadcast_to(k[None, :], due.shape)
    inside = due < seconds
    due, sid, kk = due[inside], sid[inside], kk[inside]
    order = np.argsort(due, kind="stable")
    return due[order], (starts[sid[order]] + kk[order]) % pool_size


def open_loop(stream, pool, kind: str, tier: str, mix: dict,
              seconds: float, keep: np.ndarray, rng, hooks,
              wait_s: float = 60.0) -> Record:
    from repro.serving.streaming import AdmissionError

    offs, pidx = open_schedule(mix, seconds, len(pool), rng)
    n = offs.size
    led = _Ledger(max(n, 1))
    led.pool_idx[:n] = pidx
    samples: dict = {}

    def on_done(j, fut):
        t = time.perf_counter()
        failed, out = _kept(fut)
        if not failed and keep[j % keep.size]:
            samples[j] = np.array(out, copy=True)
        led.failed[j] = failed
        led.done[j] = t

    t_open = time.perf_counter() + 0.01
    led.due[:n] = t_open + offs
    hooks.open(t_open)
    for j in range(n):
        due = led.due[j]
        wait = due - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        led.sent[j] = time.perf_counter()
        try:
            fut = stream.submit(pool[pidx[j]], kind=kind, tier=tier)
        except AdmissionError:
            led.failed[j] = led.refused[j] = True
            continue
        fut.add_done_callback(partial(on_done, j))
    t_close = t_open + seconds
    wait = t_close - time.perf_counter()
    if wait > 0:
        time.sleep(wait)
    hooks.close(time.perf_counter())
    stream.drain(timeout=wait_s)
    gave_up = time.perf_counter()
    led.failed[:n] |= np.isnan(led.done[:n])
    return led.record(n, samples, t_open, t_close, gave_up)

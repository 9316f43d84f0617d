"""The benchmark's view of the layers: objects it builds around the
program's own, with no change to the program.

* :class:`TapEngine` is the program's serving engine with a tap that
  keeps the last generated tokens, so the comparison sees what each tier
  call served.
* :class:`TierProbe` delegates to a tier like the program's
  ``ResilientTier`` (keeping the ``answer_many`` capability probe) and
  times each call on the host clock inside a ``bench.tier.<name>`` span.
* :class:`EmbedProbe` times each ``embed_fn`` call (``bench.embed``).
* :class:`FabricProxy` notes each microbatch's submit time
  (``bench.submit``) and hands its ticket to a collector thread that
  notes when it resolves.
* :class:`CompileClock` counts JAX's compile events.
"""
from __future__ import annotations

import dataclasses
import queue
import threading
import time

import jax
import numpy as np


def request_key(prompt: np.ndarray, tail: int) -> bytes:
    """A request's identity inside a tier prompt: its question tail,
    which no other request shares."""
    return np.asarray(prompt[-tail:], np.int32).tobytes()


@dataclasses.dataclass
class Call:
    tier: str
    start: float
    seconds: float
    prompts: list
    max_new: int
    tokens: np.ndarray        # (len(prompts), max_new)


def tap_engine_class(base):
    """A subclass of the program's ``ServingEngine`` whose
    ``generate_bucketed`` keeps its last output in ``last_out``."""

    class TapEngine(base):
        last_out = None

        def generate_bucketed(self, prompts, max_new):
            out = super().generate_bucketed(prompts, max_new)
            self.last_out = out
            return out

    return TapEngine


class TierProbe:
    """Delegating wrapper over one tier; every served sweep is logged as
    a :class:`Call` while ``log`` is not None."""

    def __init__(self, tier, name: str):
        self.inner = tier
        self.name = name
        self.span = f"bench.tier.{name}"
        self.log: list[Call] | None = None

    def __getattr__(self, attr):
        return getattr(object.__getattribute__(self, "inner"), attr)

    def _timed(self, fn, prompts, max_new, *args):
        t0 = time.monotonic()
        with jax.profiler.TraceAnnotation(self.span):
            out = fn(prompts, *args)
        dt = time.monotonic() - t0
        if self.log is not None:
            self.log.append(Call(self.name, t0, dt, list(prompts), max_new,
                                 np.asarray(self.inner.engine.last_out)))
        return out

    def answer_many(self, prompts):
        return self._timed(self.inner.answer_many, prompts, 1)

    def generate_guides_many(self, requests, guide_len):
        return self._timed(self.inner.generate_guides_many, requests, 2,
                           guide_len)


class EmbedProbe:
    """``embed_fn`` with host timing; keeps each embedding by request."""

    def __init__(self, fn, tail: int):
        self.fn = fn
        self.tail = tail
        self.log: list[tuple[float, float]] | None = None
        self.embs: dict[bytes, np.ndarray] = {}

    def __call__(self, prompt):
        t0 = time.monotonic()
        with jax.profiler.TraceAnnotation("bench.embed"):
            emb = np.asarray(self.fn(prompt))
        dt = time.monotonic() - t0
        if self.log is not None:
            self.log.append((t0, dt))
        self.embs[request_key(prompt, self.tail)] = emb
        return emb


@dataclasses.dataclass
class Done:
    rids: list
    submitted: float          # time.monotonic() at submit
    resolved: float | None    # time.monotonic() when its ticket resolved
    outcomes: list | None
    error: str | None = None


class FabricProxy:
    """Stands in front of the fabric for the admission scheduler: notes
    each microbatch's submit time, and a collector thread waits the
    tickets in submission order (one replica serves them in that order)
    and notes when each resolves."""

    def __init__(self, fabric):
        self.fabric = fabric
        self.batches: list[Done] = []
        self._q: queue.Queue = queue.Queue()
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._collect,
                                        name="bench-collector", daemon=True)
        self._thread.start()

    def __getattr__(self, attr):
        return getattr(object.__getattribute__(self, "fabric"), attr)

    def submit(self, prompts, guide_requests, keys=None, embs=None,
               replica=None):
        t = time.monotonic()
        with jax.profiler.TraceAnnotation("bench.submit"):
            ticket = self.fabric.submit(prompts, guide_requests, keys=keys,
                                        embs=embs, replica=replica)
        done = Done(rids=list(keys), submitted=t, resolved=None,
                    outcomes=None)
        self.batches.append(done)
        self._q.put((ticket, done))
        return ticket

    def _collect(self):
        while not self._stop.is_set():
            try:
                ticket, done = self._q.get(timeout=0.1)
            except queue.Empty:
                continue
            while not self._stop.is_set():
                try:
                    outs = ticket.wait(timeout=0.1)
                except TimeoutError:
                    continue
                except RuntimeError as e:
                    done.error = repr(e.__cause__ or e)
                    done.resolved = time.monotonic()
                    break
                done.resolved = time.monotonic()
                done.outcomes = outs
                break

    def wait_all(self, deadline: float) -> bool:
        """Block until every submitted microbatch resolved, or until
        ``deadline`` (time.monotonic()); True when all resolved."""
        while time.monotonic() < deadline:
            if all(b.resolved is not None for b in self.batches):
                return True
            time.sleep(0.005)
        return all(b.resolved is not None for b in self.batches)

    def close(self) -> None:
        self._stop.set()
        self._thread.join(timeout=5.0)


class CompileClock:
    """Seconds JAX spent compiling or loading compiled programs, how many
    programs that was, and persistent-cache hits, in this process."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    CACHE_HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        self.seconds = 0.0
        self.count = 0
        self.cache_hits = 0

        def on_duration(name, secs, **_):
            if name == self.COMPILE:
                self.seconds += secs
                self.count += 1

        def on_event(name, **_):
            if name == self.CACHE_HIT:
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

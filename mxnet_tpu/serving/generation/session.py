"""GenerationStream — the client half of one autoregressive session.

``engine.submit(prompt)`` returns one of these immediately; the engine's
continuous scheduler then delivers tokens into it as they are decoded.
Two consumption styles:

* **streaming** — iterate the stream: each ``__next__`` yields the next
  generated token as soon as it exists. A blocking iterator is also a
  CALLER-RUNS assistant (the batcher's trick, PR 5): while its token
  queue is empty it tries to run engine ticks inline instead of parking
  behind two thread handoffs, so a single closed-loop client is not
  throttled by worker wakeup latency.
* **collecting** — ``result(timeout)`` blocks for the complete token list
  (a ``concurrent.futures.Future`` under the hood — this is also the
  future the admission queue watches, so a stream failed while queued is
  dropped unadmitted).

Failure surfaces in-band: a session evicted on deadline raises
:class:`~mxnet_tpu.serving.admission.DeadlineExceededError` from the
iterator (and from ``result()``) instead of wedging it; engine errors
raise the original exception the same way.
"""
from __future__ import annotations

import queue
import time
from concurrent.futures import Future

__all__ = ["GenerationStream"]

_TOK, _END, _ERR = 0, 1, 2


class GenerationStream:
    """Iterator of generated tokens for one submitted prompt."""

    def __init__(self, engine, prompt_len, max_new_tokens, deadline=None,
                 tenant=None):
        self._engine = engine       # reassigned when a preempted session
        #                             migrates to a peer replica (the
        #                             caller-runs assist then drives the
        #                             adopting engine's ticks)
        self._q = queue.Queue()
        self._future = Future()
        self._stop = False          # iterator-side: terminal item consumed
        self.tokens = []            # delivered so far (engine appends)
        self.prompt_len = int(prompt_len)
        self.max_new_tokens = int(max_new_tokens)
        self.deadline = deadline
        self.tenant = tenant        # QoS tenant name (None = default class)
        self.submitted_at = time.monotonic()
        self.first_token_at = None
        # set at admission when the engine forked a cached prompt prefix
        # instead of running a full prefill: the number of prompt tokens
        # whose K/V came from the prefix cache (0 = full prefill) — the
        # client-visible "why was my TTFT fast" signal
        self.cached_prefix_len = 0
        # the cache slot the session was last admitted (or resumed) into;
        # after it ends, the slot keeps what it held until the next
        # occupant (GenerationEngine.slot_snapshot reads it)
        self.slot = None

    # -- engine side ---------------------------------------------------------

    def _push(self, tok):
        if self.first_token_at is None:
            self.first_token_at = time.monotonic()
        self.tokens.append(tok)
        self._q.put((_TOK, tok))

    def _finish(self):
        if not self._future.done():
            self._future.set_result(list(self.tokens))
        self._q.put((_END, None))

    def _fail(self, exc):
        if not self._future.done():
            self._future.set_exception(exc)
        self._q.put((_ERR, exc))

    # -- client side ---------------------------------------------------------

    @property
    def done(self):
        """True once the session reached a terminal state (all tokens
        delivered, or failed)."""
        return self._future.done()

    def result(self, timeout=None):
        """Block for the COMPLETE generation: the list of all generated
        tokens (raises the failure exception for failed sessions)."""
        return self._future.result(timeout)

    def __iter__(self):
        return self

    def __next__(self):
        if self._stop:
            raise StopIteration
        while True:
            try:
                kind, val = self._q.get_nowait()
                break
            except queue.Empty:
                # caller-runs assist: drive the engine inline while our
                # queue is empty; when another thread holds the tick lock
                # (the worker mid-tick), park briefly on the queue instead
                if not self._engine._assist_once():
                    try:
                        kind, val = self._q.get(timeout=0.005)
                        break
                    except queue.Empty:
                        continue
        if kind == _TOK:
            return val
        self._stop = True
        if kind == _ERR:
            raise val
        raise StopIteration

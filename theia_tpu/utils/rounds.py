"""Rounds that fall on a timer and can also be asked for: the one
wait protocol of the supervised background loops (the Checkpointer,
store/checkpoint.py; the RetentionLoop, store/flow_store.py).

One thread runs the rounds, one at a time, and numbers them as they
start. Its loop is

    due = clock() + interval
    while (run := rounds.next(due)) is not None:
        result = one_round()
        due = clock() + interval          # a round is the tick
        rounds.done(run, result)

Any other thread can `ask()` for a round: it wakes the loop, waits out
a round already under way, and gets the result of the first round
that STARTS after it asked.
"""

from __future__ import annotations

import threading
import time
from typing import Callable, Dict, Optional, Type


class AskedRounds:
    """`who` runs the rounds and `what` is one of them (both for the
    messages); `unavailable` is raised by `ask()` when no round can be
    asked for; `clock` times the waits (injectable for tests)."""

    def __init__(self, who: str, what: str,
                 unavailable: Type[Exception],
                 clock: Callable[[], float] = time.monotonic) -> None:
        self.who, self.what = who, what
        self.unavailable = unavailable
        self.clock = clock
        #: rounds started and finished so far
        self.started = 0
        self.finished = 0
        #: the last finished round's result
        self.last: Optional[Dict[str, object]] = None
        self._cond = threading.Condition()
        self._wanted = 0
        self._open = False
        self._results: Dict[int, Dict[str, object]] = {}

    def open(self) -> None:
        """The loop's thread is about to start: rounds can be asked
        for from now on."""
        with self._cond:
            self._open = True

    def stop(self) -> None:
        """End the loop (its `next()` answers None) and every wait."""
        with self._cond:
            self._open = False
            self._cond.notify_all()

    @property
    def running(self) -> bool:
        with self._cond:
            return self.started > self.finished

    # -- the loop's thread ------------------------------------------------

    def next(self, due: float) -> Optional[int]:
        """Wait until `due` (on the clock) or until a round is asked
        for; the number of the round that starts now, None once
        stopped."""
        with self._cond:
            while self._open and self._wanted <= self.started:
                left = due - self.clock()
                if left <= 0:
                    break
                self._cond.wait(left)
            if not self._open:
                return None
            self.started += 1
            return self.started

    def done(self, run: int, result: Dict[str, object]) -> None:
        with self._cond:
            self.finished = run
            self.last = result
            # kept for the requests that wait for this round (they
            # wake on the notify below), not for longer
            self._results[run] = result
            self._results.pop(run - 2, None)
            self._cond.notify_all()

    # -- any other thread -------------------------------------------------

    def ask(self, timeout: Optional[float] = None) -> Dict[str, object]:
        """Ask for a round now and wait for it. Raises `unavailable`
        when the loop is not running or stops meanwhile, TimeoutError
        after `timeout` seconds."""
        deadline = None if timeout is None else self.clock() + timeout
        with self._cond:
            if not self._open:
                raise self.unavailable(f"the {self.who} is not running")
            run = self.started + 1
            self._wanted = max(self._wanted, run)
            self._cond.notify_all()
            while self.finished < run:
                if not self._open:
                    raise self.unavailable(f"the {self.who} stopped")
                left = None if deadline is None \
                    else deadline - self.clock()
                if left is not None and left <= 0:
                    raise TimeoutError(
                        f"no {self.what} within {timeout:g}s")
                self._cond.wait(left)
            return self._results.get(run, self.last)

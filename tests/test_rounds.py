"""utils/rounds.py `AskedRounds`: the wait protocol the Checkpointer
and the RetentionLoop share (their own tests drive it through them:
tests/test_checkpoint_request.py, tests/test_retention_request.py)."""

import threading
import time

import pytest

from theia_tpu.utils.rounds import AskedRounds


class Nope(Exception):
    pass


class Loop:
    """A loop as the module's docstring writes it; a round takes until
    `release` is set and answers its number."""

    def __init__(self, interval=60.0):
        self.rounds = AskedRounds("looper", "round", Nope)
        self.interval = interval
        self.release = threading.Event()
        self.release.set()
        self.entered = threading.Event()
        self.thread = threading.Thread(target=self.run, daemon=True)

    def run(self):
        due = time.monotonic() + self.interval
        while (n := self.rounds.next(due)) is not None:
            self.entered.set()
            self.release.wait(10)
            due = time.monotonic() + self.interval
            self.rounds.done(n, {"round": n})

    def __enter__(self):
        self.rounds.open()
        self.thread.start()
        return self

    def __exit__(self, *exc):
        self.rounds.stop()
        self.thread.join(10)
        assert not self.thread.is_alive()


def test_no_round_can_be_asked_for_before_open_or_after_stop():
    loop = Loop()
    with pytest.raises(Nope, match="the looper is not running"):
        loop.rounds.ask(timeout=1)
    with loop:
        assert loop.rounds.ask(timeout=10) == {"round": 1}
    with pytest.raises(Nope, match="the looper is not running"):
        loop.rounds.ask(timeout=1)


def test_an_asked_round_starts_after_the_request():
    """A round under way is waited out; the answer is the next one's,
    and two requests that arrive during one round share the next."""
    with Loop() as loop:
        loop.release.clear()
        got = []
        first = threading.Thread(
            target=lambda: got.append(loop.rounds.ask(timeout=10)))
        first.start()
        assert loop.entered.wait(10) and loop.rounds.running
        later = [threading.Thread(
            target=lambda: got.append(loop.rounds.ask(timeout=10)))
            for _ in range(2)]
        for t in later:
            t.start()
        time.sleep(0.1)
        assert not got                    # one at a time
        loop.release.set()
        for t in [first] + later:
            t.join(10)
        assert sorted(g["round"] for g in got) == [1, 2, 2]
        assert (loop.rounds.started, loop.rounds.finished) == (2, 2)
        assert loop.rounds.last == {"round": 2} and not loop.rounds.running


def test_the_timer_runs_rounds_nobody_asked_for():
    with Loop(interval=0.05) as loop:
        deadline = time.monotonic() + 5
        while loop.rounds.finished < 3 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert loop.rounds.finished >= 3


@pytest.mark.parametrize("how", ["timeout", "stop"])
def test_a_wait_ends_without_its_round(how):
    with Loop() as loop:
        loop.release.clear()
        if how == "timeout":
            with pytest.raises(TimeoutError, match="no round within 0.2s"):
                loop.rounds.ask(timeout=0.2)
        else:
            threading.Timer(0.2, loop.rounds.stop).start()
            with pytest.raises(Nope, match="the looper stopped"):
                loop.rounds.ask(timeout=10)
        loop.release.set()

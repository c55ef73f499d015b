"""Host stack sampler: what the program's threads were doing, and when.

A daemon thread reads every thread's innermost frame every ``interval``
seconds and keeps ``(time.time_ns(), label)``, where the label is the
innermost function of the program under test (``module:function``, the
module relative to the package root) on the stack of a thread that is
running, or ``host idle`` when every such thread waits.  Threads whose
innermost frame is a blocking wait of the standard library are waiting;
threads named in ``skip`` (the load generator) are left out.

It reads stacks from outside and needs nothing of the program; its cost
is one ``sys._current_frames()`` per sample.
"""
from __future__ import annotations

import os
import sys
import threading
import time
from typing import List, Sequence, Tuple

_WAITS = {"wait", "acquire", "get", "accept", "recv", "recv_into", "select",
          "poll", "sleep", "join", "_wait_for_tstate_lock", "readinto",
          "result"}


class StackSampler:
    def __init__(self, package_dir: str, skip: Sequence[str] = (),
                 interval: float = 0.005):
        self.root = os.path.dirname(os.path.abspath(package_dir)) + os.sep
        self.skip = tuple(skip)
        self.interval = interval
        self.samples: List[Tuple[int, str]] = []
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, name="bench-sampler",
                                        daemon=True)

    def _label(self, frame) -> str:
        if frame.f_code.co_name in _WAITS and not frame.f_code.co_filename \
                .startswith(self.root):
            return ""
        while frame is not None:
            path = frame.f_code.co_filename
            if path.startswith(self.root):
                mod = os.path.splitext(path[len(self.root):])[0]
                return f"{mod.replace(os.sep, '.')}:{frame.f_code.co_name}"
            frame = frame.f_back
        return ""

    def _run(self) -> None:
        me = threading.get_ident()
        while not self._stop.wait(self.interval):
            names = {t.ident: t.name for t in threading.enumerate()}
            labels = sorted(
                lab for ident, frame in sys._current_frames().items()
                if ident != me and not names.get(ident, "").startswith(
                    self.skip)
                for lab in [self._label(frame)] if lab)
            self.samples.append((time.time_ns(),
                                 labels[0] if labels else "host idle"))

    def __enter__(self) -> "StackSampler":
        self._thread.start()
        return self

    def __exit__(self, *exc) -> None:
        self._stop.set()
        self._thread.join()

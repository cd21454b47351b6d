"""Fixture: the ``# pipecheck: disable=<rule>`` comment path."""

import os
import threading


class SuppressedPool:
    def __init__(self):
        self._lock = threading.Lock()
        self._queue = None

    def drain(self):
        with self._lock:
            # justified: fixture for the suppression syntax itself
            self._queue.get()  # pipecheck: disable=blocking-under-lock


# suppressed via `all`
_RAW = os.environ.get('PETASTORM_TPU_STAGING_SLOTS')  # pipecheck: disable=all

"""How ``correct`` is decided for the RST-engine cells.

Every kernel call served in the window leaves its checksum (see
``rst_capture``).  Once the window has closed, a sample of the calls drawn
from the seed is compared with the plain reference
(``references/rst_checksum.py``): the widest checksum gap, which is 0 for a
call that read exactly the bursts of its stream, in order, in float32; and
the number of calls whose byte count is not the stream's.  Each answer is
also held to the kernel calls it was served from: a request whose GB/s are
not, one for one, what its own calls measured counts as a mismatch.
"""
from __future__ import annotations

from bench.references import rst_checksum as reference
from bench.rst_capture import KernelCapture


class Capture:
    def __init__(self, config: dict):
        self.config = config
        self.kernels = KernelCapture().install()
        self.clear()

    def clear(self) -> None:
        self.kernels.calls.clear()
        self._seen = 0
        self.answers_off = 0

    def answered(self, lookup, values) -> None:
        """The served GB/s are measurements with no reference; each must be
        the bytes over the seconds of one kernel call of this request."""
        calls = self.kernels.calls[self._seen:]
        self._seen = len(self.kernels.calls)
        self.answers_off += int(sorted(values)
                                != sorted(c["gbps"] for c in calls))

    def sample(self, rng) -> list:
        calls = self.kernels.calls
        k = min(int(self.config["check_calls"]), len(calls))
        return [calls[int(i)] for i in
                sorted(rng.choice(len(calls), size=k, replace=False))]

    def check(self, rng, control: bool = False) -> dict:
        calls = self.sample(rng)
        if control:
            calls = reference.control(calls, self.config)
        readings = reference.compare(calls, self.config)
        if not calls:       # a window that served no kernel call fails
            readings["checksum_gap"] = float("inf")
        readings["answer_mismatch"] = float(self.answers_off)
        return {name: {"value": float(value),
                       "limit": float(self.config["limits"][name])}
                for name, value in readings.items()}

    def close(self) -> None:
        self.kernels.uninstall()

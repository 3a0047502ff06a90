"""The daemon's doors as the checks use them (not the timed path, which
belongs to the generators): one blocking call -> [(status, limit,
remaining)], and bulk calls over GEB for the preload."""

from __future__ import annotations

CALL_TIMEOUT = 120.0


def _norm(resps, reqs):
    out = []
    for r, q in zip(resps, reqs):
        if r.error:
            raise RuntimeError(f"'{q.unique_key}' answered error {r.error!r}")
        out.append((int(r.status), int(r.limit), int(r.remaining)))
    if len(out) != len(reqs):
        raise RuntimeError(f"{len(resps)} answers for {len(reqs)} items")
    return out


class Doors:
    def __init__(self, daemon):
        from gubernator_tpu.client import V1Client
        from gubernator_tpu.client_geb import GebClient

        self._v1 = V1Client(daemon.grpc)
        self._geb = GebClient(daemon.geb, timeout=CALL_TIMEOUT)
        self._geb.connect()

    def close(self) -> None:
        self._geb.close()
        self._v1.close()

    def call(self, door: str, reqs):
        if door == "grpc":
            return _norm(self._v1.get_rate_limits(reqs, timeout=CALL_TIMEOUT), reqs)
        if door == "geb":
            return _norm(self._geb.get_rate_limits(reqs), reqs)
        raise ValueError(f"unknown door '{door}'")

    def bulk(self, batches):
        """Many GEB frames, a credit window of them in flight."""
        return [
            _norm(resps, reqs)
            for reqs, resps in zip(
                batches, self._geb.get_rate_limits_pipelined(batches)
            )
        ]

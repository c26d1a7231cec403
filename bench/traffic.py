"""The one traffic generator: every mix is a data file
(``bench/traffic/<mix>.json``) of parameters that this module reads.

Keys of a mix file:

* ``loop``: ``"open"`` (arrivals on a schedule in seconds, each request
  timed from when it was due) or ``"closed"`` (each client sends its next
  request as soon as it sees the previous one done).
* ``rate_rps`` (open): offered requests per second.  The count of a run is
  fixed, ``round(rate_rps * (warmup_s + seconds))``, and the arrival
  times are that many uniform draws over the span, sorted: a Poisson
  process conditioned on its count, so every seed offers the same work.
* ``clients_per_slot`` (closed): clients per pool slot of the fleet.
* ``max_len``: the engine's length budget; a request is served
  ``max_len - 1`` tokens unless it emits the end token first.
* ``warmup_s``: traffic run before the measured window, as set-up.
* ``classes``: ``{"service", "share", "headers"}``; a header value
  ``"$user"`` takes the request's end user.
* ``users`` (optional): ``{"count", "zipf_s", "named": {name: share}}``:
  end users ranked by a Zipf law, plus named users with fixed shares.
* ``msg_bytes``: ``[lo, hi)`` request payload sizes.
* ``why``, ``source``, ``assumed`` and other keys: what the mix stands
  for and where its numbers come from; the generator does not read them.

Every draw is keyed by the seed and the request's index (open) or by the
seed, client and the client's request number (closed), so the same seed
gives the same requests whatever order the run takes them in.
"""

from __future__ import annotations

import dataclasses

import numpy as np

TEMPLATES = 1 << 16          # closed-loop request templates per run


@dataclasses.dataclass
class Requests:
    """Columns of drawn requests (one row each)."""

    cls: np.ndarray          # class index
    user: np.ndarray         # end-user index into ``Mix.user_names``
    token: np.ndarray        # prompt token
    msg_bytes: np.ndarray


class Mix:
    """A traffic mix file bound to a seed and a vocabulary."""

    def __init__(self, spec: dict, seed: int, vocab: int):
        self.spec = spec
        self.seed = int(seed)
        self.vocab = vocab
        self.loop = spec["loop"]
        if self.loop not in ("open", "closed"):
            raise ValueError(f"unknown loop {self.loop!r}")
        self.max_len = int(spec["max_len"])
        self.warmup_s = float(spec.get("warmup_s", 1.0))
        self.classes = spec["classes"]
        share = np.array([c["share"] for c in self.classes], np.float64)
        self.class_p = share / share.sum()
        users = spec.get("users")
        if users:
            n = int(users["count"])
            w = 1.0 / np.arange(1, n + 1) ** float(users["zipf_s"])
            named = users.get("named", {})
            rest = 1.0 - sum(named.values())
            self.user_names = [f"user{k}" for k in range(1, n + 1)] \
                + list(named)
            self.user_p = np.concatenate([w / w.sum() * rest,
                                          list(named.values())])
        else:
            self.user_names, self.user_p = ["anonymous"], np.ones(1)
        self.user_cdf = np.cumsum(self.user_p)
        self.user_cdf[-1] = 1.0

    def rng(self, *stream: int) -> np.random.Generator:
        return np.random.default_rng([self.seed, *stream])

    def draw(self, n: int, stream: int) -> Requests:
        """``n`` requests' attributes from one keyed stream."""
        g = self.rng(stream)
        cls = g.choice(len(self.classes), size=n, p=self.class_p)
        user = np.minimum(np.searchsorted(self.user_cdf, g.random(n)),
                          len(self.user_names) - 1)
        lo, hi = self.spec["msg_bytes"]
        return Requests(cls, user, g.integers(2, self.vocab, n),
                        g.integers(lo, hi, n))

    def headers(self, cls: int, user: int) -> dict:
        name = self.user_names[user]
        return {k: (name if v == "$user" else v)
                for k, v in self.classes[cls]["headers"].items()}

    # ---- open loop --------------------------------------------------- #
    def schedule(self, seconds: float) -> tuple[np.ndarray, Requests]:
        """(sorted due times in seconds from the start of traffic, the
        requests) for a warm-up plus ``seconds`` of window."""
        span = self.warmup_s + seconds
        n = int(round(float(self.spec["rate_rps"]) * span))
        due = np.sort(self.rng(1).random(n) * span)
        return due, self.draw(n, 2)

    # ---- closed loop ------------------------------------------------- #
    def clients(self, n_slots: int) -> int:
        return int(round(float(self.spec["clients_per_slot"]) * n_slots))

    def templates(self) -> Requests:
        return self.draw(TEMPLATES, 3)

    @staticmethod
    def template_index(client: int, k: int) -> int:
        """The template the ``k``-th request of ``client`` takes."""
        return (client * 1_000_003 + k * 7919) % TEMPLATES

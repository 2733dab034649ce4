"""Adaptive speculation-depth control for the serving engine's spec mode.

Speculative decoding only wins while the drafter is usually right: a spec
step costs k drafter forwards plus one (k+1)-wide verify, and emits
``1 + acceptance * k`` tokens in expectation. With low acceptance the
drafter work is pure loss — k must shrink, and (for the self-drafting
backend) retreat to plain decode entirely. With high acceptance every
extra accepted draft amortizes one more weight stream over HBM — k should
grow back toward the configured cap.

:class:`AdaptiveK` is the host-side controller: it EWMA-tracks the
per-verify-step acceptance ratio the engine feeds it, walks k up/down a
pow2 ladder (bounded program set: one compiled spec-step program per
ladder rung) with a cooldown between moves, and — when even k=1 loses —
suspends speculation (``current() == 0``), re-probing after a fixed number
of plain chunks so a workload shift (e.g. a prompt family the drafter
models well) is rediscovered.

The draft-model backend never suspends (``allow_off=False``): its
separate KV cache is only coherent while the drafter sees every decoded
token, and plain chunks would starve it — k floors at 1 instead. That
floor is also its failure mode: a mismatched draft checkpoint
(ROADMAP D4: its acceptance was low wherever it was counted) pins k=1 and
pays a full drafter forward per step forever. ``min_accept`` is the
retreat for THAT backend — sustained EWMA acceptance below the floor
after the warm-up cooldown **permanently disables** drafting
(``current() == 0``, no re-probe: the checkpoint will not get better),
so enabling ``draft`` on the wrong model degrades to plain decode
instead of a latent regression. The engine logs one warning and exports
``kubeml_serving_spec_disabled`` on the transition.
"""

from __future__ import annotations

from typing import List


class AdaptiveK:
    """EWMA acceptance tracker + pow2 k-ladder walker.

    ``on_step(drafted, accepted)`` after every processed spec step;
    ``on_plain_chunk()`` after every plain chunk while suspended;
    ``current()`` is the k the next spec dispatch should use (0 =
    suspended, dispatch a plain chunk instead).
    """

    # acceptance thresholds: below ``low`` k halves (k=1 suspends when
    # allowed); above ``high`` k doubles toward the cap. The gap is the
    # hysteresis band. Rough math for the defaults: a self-drafting step
    # at exit depth e of D costs ~``k * e/D + 1`` target-forward
    # equivalents for ``1 + a*k`` expected tokens, so with e/D ~ 1/2 the
    # break-even acceptance is ~1/2 — 0.35 retreats comfortably below it,
    # 0.8 only grows when speculation is clearly paying.
    LOW = 0.35
    HIGH = 0.80

    def __init__(self, k_max: int, *, adaptive: bool = True,
                 allow_off: bool = True, low: float = LOW,
                 high: float = HIGH, ewma: float = 0.2,
                 cooldown: int = 8, probe_every: int = 64,
                 min_accept: float = 0.0):
        if k_max < 1:
            raise ValueError("k_max must be >= 1")
        if not (0.0 <= min_accept < 1.0):
            raise ValueError("min_accept must be in [0, 1)")
        ladder = []
        t = 1
        while t < k_max:
            ladder.append(t)
            t *= 2
        ladder.append(int(k_max))
        self.ladder: List[int] = ladder  # ascending, ends at k_max
        self.adaptive = bool(adaptive)
        self.allow_off = bool(allow_off)
        self.low = float(low)
        self.high = float(high)
        self.alpha = float(ewma)
        self.cooldown = int(cooldown)
        self.probe_every = int(probe_every)
        self.min_accept = float(min_accept)
        self._idx = len(ladder) - 1  # start at the configured cap
        self._suspended = False
        self._ratio: float = -1.0    # EWMA; <0 = no sample yet
        self._since_move = 0
        self._steps_seen = 0
        self._plain_chunks = 0
        # telemetry (engine snapshots these)
        self.moves = 0
        self.suspensions = 0
        # the draft-mode acceptance-floor kill switch: once tripped it
        # never re-arms (suspension re-probes; this does not)
        self.disabled = False

    def current(self) -> int:
        """The k the next spec dispatch should use; 0 = suspended or
        permanently disabled (the min_accept floor tripped)."""
        if self.disabled:
            return 0
        return 0 if self._suspended else self.ladder[self._idx]

    @property
    def ratio(self) -> float:
        """The EWMA acceptance ratio (-1 before the first sample)."""
        return self._ratio

    def on_step(self, drafted: int, accepted: int) -> None:
        """Feed one processed spec step's device-truth acceptance."""
        if drafted <= 0:
            return
        r = accepted / drafted
        self._ratio = (r if self._ratio < 0
                       else self.alpha * r + (1 - self.alpha) * self._ratio)
        self._steps_seen += 1
        # the acceptance floor fires regardless of ``adaptive``: it guards
        # a broken configuration, not a workload phase. The cooldown worth
        # of samples lets the EWMA settle before judging.
        if (self.min_accept > 0.0 and not self.disabled
                and self._steps_seen >= self.cooldown
                and self._ratio < self.min_accept):
            self.disabled = True
            return
        if not self.adaptive:
            return
        self._since_move += 1
        if self._since_move < self.cooldown:
            return
        if self._ratio < self.low:
            if self._idx > 0:
                self._idx -= 1
                self.moves += 1
            elif self.allow_off and not self._suspended:
                self._suspended = True
                self._plain_chunks = 0
                self.suspensions += 1
            self._since_move = 0
        elif self._ratio > self.high and self._idx < len(self.ladder) - 1:
            self._idx += 1
            self.moves += 1
            self._since_move = 0

    def on_plain_chunk(self) -> None:
        """While suspended, count plain chunks toward the re-probe."""
        if not self._suspended:
            return
        self._plain_chunks += 1
        if self._plain_chunks >= self.probe_every:
            # probe at the bottom rung with a fresh estimate: the old EWMA
            # is what suspended us and must not instantly re-suspend
            self._suspended = False
            self._idx = 0
            self._ratio = -1.0
            self._since_move = 0

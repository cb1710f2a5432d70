"""Disaggregated prefill/decode serving over the unified KVTier stack.

Dedicated **prefill engines** (:class:`~repro.disagg.prefill.
PrefillEngine`) turn requests into published hash chains in a shared
:class:`~repro.cache.PrefixCache`; **decode sessions** (plain
:class:`~repro.serving.api.ServeSession`\\ s over the same cache) restore
those chains at admission and sample tokens.  The
:class:`~repro.disagg.frontend.DisaggFrontEnd` connects the two pools
with a handoff queue of :class:`~repro.disagg.ticket.PrefillTicket`\\ s,
steps both in modeled-clock lockstep, and stretches the fault ladder
across the boundary: a chain found corrupt at handoff is quarantined and
its ticket re-queued for a bounded re-prefill — a decode row is never
admitted from a quarantined chain.

Usage::

    cache = PrefixCache(dir, PrefixCacheConfig())
    prefills = [PrefillEngine(f"p{i}", model, params, cfg, cache=cache)
                for i in range(2)]
    decode = ServeSession(model, params, cfg, slots=4, prefix_cache=cache)
    front = DisaggFrontEnd(prefills, [decode], cache=cache)
    rid = front.submit({"prompt": ids, "max_tokens": 32})
    front.drain()
    tokens = front.result(rid)

See docs/architecture.md ("Disaggregated serving") for the ticket
lifecycle and the tier-chain walk, and docs/tuning.md for the knobs.
"""

from repro.disagg.frontend import DisaggFrontEnd
from repro.disagg.prefill import PrefillEngine
from repro.disagg.ticket import (ADMITTED, DONE, FAILED, QUEUED, READY,
                                 PrefillTicket)

__all__ = ["ADMITTED", "DONE", "DisaggFrontEnd", "FAILED", "PrefillEngine",
           "PrefillTicket", "QUEUED", "READY"]

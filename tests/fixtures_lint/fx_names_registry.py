"""Miniature telemetry-name registry for the name-checker fixtures.
AST-parsed only."""

SPANS = frozenset({
    "fx.request",
})

EVENTS = frozenset({
    "fx.evt",
})

COUNTERS = frozenset({
    "fx.known",
    "fx.reasons.alpha",
    "fx.undocumented",   # absent from fx_names_doc.md: DTL042
    "fx.wait",           # PREFIX of the documented `fx.wait_s`: still
                         # DTL042 — doc matching is whole-token, not
                         # substring
})

GAUGES = frozenset({
    "fx.level",
})

HISTOGRAMS = frozenset({
    "fx.wait_s",
})

DEVICE_SCOPES = frozenset({
    "fx_scope",
    "fx_attn.alpha",
})

KERNEL_NAMES = frozenset({
    "fx_kernel_fwd",
})

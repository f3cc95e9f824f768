"""Repo tooling: the flight-recorder query CLI (`obsq`), the tracer
span cost probe (`span_cost`), and the static invariant checker
(`tools.crdtlint`)."""

"""Unified observability plane (DESIGN.md §12).

In-scan metric rings (`metrics`), grant-lifecycle event logs (`spans`),
and host-side JSON-lines / perfetto export with the profiler span registry
(`export`), shared by the serving engine and the JBOF sim. Import the
module you need: `spans` imports `repro.core`, and `core.manager` imports
`export`, so the package imports none of them itself.
"""

"""Readers of per-layer metrics, one file a kind. A layer metric's data file
(`layer_metrics/<metric>.json`) names its reader and gives its parameters.
`read(params, ctx)` returns the number, or None where it finds nothing to
read (the harness then leaves the metric out; it never becomes 0)."""

"""Hand tools of the benchmark (never imported by a measured run
except to dump a trace on request)."""

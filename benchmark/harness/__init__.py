"""The benchmark's own code: data and load generation, the reduction from
traces, spans and counters to metrics, and the comparison that decides
`correct`. Nothing here is imported by the program."""

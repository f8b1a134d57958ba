"""Readers of the program's tables at given rows, one module per model
family, found by the configuration's `family` as the references and the
work functions are. This is the one place where the benchmark looks inside
a trainer: `params` and `opt_state` as its checkpoints store them. A family
or a layout that is new brings a file here, beside its reference; nothing
that is here is edited. Gathers run on the device, so a sharded table is
gathered across its chips."""

"""The work a step or a scored batch needs, per family, as functions of the
configuration's shapes and the traffic's counts alone. No term follows
what an implementation chooses to do (a dense pass, a packed row, a
padded lane): these are what a share of the chip's peak is measured
against, and they have to stay put when the implementation changes."""

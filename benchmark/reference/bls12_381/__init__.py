"""Plain BLS12-381 arithmetic in Python integers: fields, curves, RFC 9380
hash-to-G2 and the optimal ate pairing.

A frozen copy of the package's pure-Python oracle, kept with the
benchmark so that the traffic generator and the reference verdicts
import nothing of the program under test and no change to the program
can move them. None of it touches JAX or the device.
"""

"""The benchmark's plain reference: BLS signing and verification and the
SSZ roots that signatures are taken over, independent of the program."""

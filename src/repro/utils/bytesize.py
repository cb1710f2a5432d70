"""Byte-size helpers used across memory accounting and the tuner."""

KiB = 1024
MiB = 1024 * KiB
GiB = 1024 * MiB


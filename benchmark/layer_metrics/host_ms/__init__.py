"""Readers of the metrics ``host_ms.<part>``: the harness imports a
metric's reader by its name, so a dotted name is a module of this
package: median milliseconds of a host span of the program."""

"""Readers of the metrics ``dev_ms.<part>``: the harness imports a
metric's reader by its name, so a dotted name is a module of this
package: device milliseconds under a scope of the program."""

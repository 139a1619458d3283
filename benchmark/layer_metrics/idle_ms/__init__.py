"""Readers of the metrics ``idle_ms.<part>``: the harness imports a
metric's reader by its name, so a dotted name is a module of this
package: device idle milliseconds a step inside a host span of the program."""

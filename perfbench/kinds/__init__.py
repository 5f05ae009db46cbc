"""Traffic kinds: ``kinds/<kind>.py`` defines ``Driver(cell)`` with
``setup()``, ``window(seconds)``, ``release()`` and ``check()``; a traffic
file names its kind, and the driver reads the rest of its parameters."""

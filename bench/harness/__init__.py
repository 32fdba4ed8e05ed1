"""The benchmark's harness: what is shared by every cell.  Whatever belongs
to one configuration, traffic mix or metric lives in a file of its own under
``bench/configs``, ``bench/traffic`` and ``bench/metrics``."""

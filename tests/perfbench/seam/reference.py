"""The seam configuration's reference: the VITS reference under a file of
its own, which is the file the comparison loads.  (The VITS comparison's
controls patch the module that defines ``conv``, so they do not work
through this re-export; the seam runs none.)"""

from perfbench.reference.vits_ref import *  # noqa: F401,F403

NAME = "seam"

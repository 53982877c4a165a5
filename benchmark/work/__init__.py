"""What the algorithm needs: operations and bytes from shapes. One file a
kernel, found by the name in a layer metric's file, or a whole step,
found by the name a configuration gives under `step_work`."""

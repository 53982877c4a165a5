"""What the algorithm needs: operations and bytes from shapes. One file a
kernel or a whole step, found by the name in a layer metric's file."""

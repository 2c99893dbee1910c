// Copy of the user's kernel as the program ships it (chip_smoke.py TRIAD_SRC, McCalpin's STREAM triad); the benchmark keeps its own so that the cell does not change when the program's examples do.
__kernel void triad(__global float* a, __global float* b, __global float* c,
                    float s) {
    int i = get_global_id(0);
    c[i] = a[i] + s * b[i];
}
